# Nothing is imported here: `code2vec_tpu.models.registry` is read by
# config.py, which stays free of jax at import time.
