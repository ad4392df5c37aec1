#!/usr/bin/env python3
"""Run the benchmark's command on CPU devices, for the tests only.

    python3 benchmark/tests/cpu_run.py <root> <devices> -- <run.py arguments>

`<root>` is a checkout (or a temporary copy of `BENCHMARK.json` and
`benchmark/`); `<devices>` is how many virtual CPU devices JAX is given.
The override of the harness's look for a chip lives here, in the test,
and nowhere in the benchmark. `BENCH_TEST_FAULT` plants a fault under
the timed path (see `plant_fault`).
"""

import os
import sys


def plant_fault(name: str) -> None:
    """Break `Code2VecModel._train_step` underneath the harness."""
    import jax
    import numpy as np

    from code2vec_tpu.models import jax_model

    make = jax_model.make_train_step

    def broken(*a, **kw):
        step = make(*a, **kw)
        if name == "state_unchanged":
            def wrapped(params, opt_state, batch, rng):
                _p, _o, loss = step(
                    *jax.tree_util.tree_map(lambda x: x.copy(),
                                            (params, opt_state)),
                    batch, rng)
                return params, opt_state, loss
        elif name in ("half_batch", "no_exchange"):
            def wrapped(params, opt_state, batch, rng):
                w = batch[5]
                n = w.shape[0]
                keep = n // 2 if name == "half_batch" else \
                    n // len(jax.devices())
                w = w * (np.arange(n) < keep)
                return step(params, opt_state, batch[:5] + (w,), rng)
        else:
            raise SystemExit(f"unknown fault {name!r}")
        return wrapped

    jax_model.make_train_step = broken


def main() -> int:
    root, devices = os.path.abspath(sys.argv[1]), int(sys.argv[2])
    argv = sys.argv[sys.argv.index("--") + 1:]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}")
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import run

    def cpu_devices(chips):
        import jax
        assert len(jax.devices()) == chips, (jax.devices(), chips)
        return jax.devices()

    run.require_chips = cpu_devices
    if os.environ.get("BENCH_TEST_FAULT"):
        sys.path.insert(0, run.ROOT if os.path.isdir(
            os.path.join(run.ROOT, "code2vec_tpu")) else os.getcwd())
        plant_fault(os.environ["BENCH_TEST_FAULT"])
    try:
        return run.main(argv)
    except run.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
