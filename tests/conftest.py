"""Test env: force an 8-device virtual CPU platform BEFORE jax import so
multi-chip sharding tests run without TPU hardware (SURVEY.md §5
"multi-node without a cluster")."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import signal  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


def _live_child_pids() -> set:
    """PIDs of this process's LIVE (non-zombie) direct children, via
    /proc. Zombies are excluded: a finished worker the Popen object
    hasn't reaped yet is not a leak, just bookkeeping."""
    me = os.getpid()
    out = set()
    try:
        pids = [int(p) for p in os.listdir("/proc") if p.isdigit()]
    except OSError:  # non-procfs platform: guard degrades to a no-op
        return out
    for pid in pids:
        try:
            with open(f"/proc/{pid}/stat", "rb") as f:
                stat = f.read().decode("ascii", "replace")
        except OSError:
            continue
        # field 3 = state, field 4 = ppid (after the parenthesized comm,
        # which may itself contain spaces — split from the LAST ')')
        rest = stat.rsplit(")", 1)[-1].split()
        if len(rest) >= 2 and rest[0] != "Z" and int(rest[1]) == me:
            out.add(pid)
    return out


@pytest.fixture(autouse=True)
def no_leaked_subprocesses():
    """Multiprocess-test hygiene (ISSUE 9): no test may leak a worker
    subprocess past its teardown. The 2-process Gloo harnesses
    (tests/mp_worker.py, tools/multichip_bench.py) kill their workers
    in `finally`; this guard asserts the discipline repo-wide — an
    orphaned worker would otherwise hold the coordinator port and CPU
    for the rest of the suite. Leaked processes are SIGKILLed before
    the assertion so one failure can't cascade."""
    before = _live_child_pids()
    yield
    leaked = set()
    for _ in range(20):  # grace for children mid-exit
        leaked = _live_child_pids() - before
        if not leaked:
            return
        time.sleep(0.05)
    procs = []
    for pid in leaked:
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(
                    "utf-8", "replace").strip()
        except OSError:
            cmd = "?"
        procs.append(f"{pid}: {cmd}")
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    pytest.fail("test leaked live subprocess(es) past teardown "
                f"(killed): {'; '.join(procs)}")
