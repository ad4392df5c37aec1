#!/usr/bin/env bash
# Train driver — reference-compatible (SURVEY.md §3 "Train driver"):
# set the dataset name/paths, invoke code2vec.py. --backend is a demand:
# the default `tpu` exits when JAX finds no TPU; `backend=cpu ./train.sh`
# (with JAX_PLATFORMS=cpu) runs on the CPU on purpose. Compiled programs
# are cached in $JAX_COMPILATION_CACHE_DIR, or <checkout>/.jax_cache.
set -euo pipefail

type=${type:-java-small}
dataset_name=${dataset_name:-${type}}
data_dir=${data_dir:-data}
data=${data_dir}/${dataset_name}/${dataset_name}
test_data=${data_dir}/${dataset_name}/${dataset_name}.val.c2v
model_dir=${model_dir:-models/${dataset_name}}

mkdir -p "${model_dir}"
set -x
python3 code2vec.py --data "${data}" --test "${test_data}" \
  --save "${model_dir}/saved_model" --backend "${backend:-tpu}" "$@"
