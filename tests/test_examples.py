"""Examples smoke tests: the demo scripts run against the public API.

The demos are documentation that executes -- these tests run them as
subprocesses exactly as the README tells users to, so the examples can
never drift from the API surface again (an API change that breaks a
demo breaks the suite).
"""

import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(script, *args, device_count=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    if device_count is not None:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={device_count}")
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=timeout,
    )
    assert res.returncode == 0, (
        f"{script} failed:\n{res.stdout}\n{res.stderr}")
    return res.stdout


def test_quickstart_runs():
    out = run_example("quickstart.py", "9", "4")
    assert "verified" in out
    assert "comm plan/execute" in out
    assert out.strip().endswith("OK")


@pytest.mark.multidevice
def test_collective_demo_runs():
    out = run_example("collective_demo.py", device_count=8)
    assert "CollectivePlan broadcast" in out
    assert "pytree broadcast" in out
    assert "allgatherv" in out
    assert out.count("OK") >= 4


# ------------------------------------------------------------ chip smoke
#
# chip_smoke.py refuses to run off TPU; its phase functions are driven
# here at tiny sizes on the CPU (Pallas in interpret mode), so a wrong
# path, argument or reference shows up before any chip time is spent.

TINY = dict(bucket_bytes=8 * 2048 * 4, qbucket_bytes=8 * 8192 * 4)


def _chip_smoke():
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import chip_smoke

    return chip_smoke


def test_chip_smoke_one_chip_phase_tiny():
    recs = _chip_smoke().one_chip_phase(0, **TINY)
    kinds = {(r["kind"], r["p"], r["backend"]) for r in recs}
    for p in (4, 3):
        for kind in ("reduce", "broadcast"):
            assert {(kind, p, "jnp"), (kind, p, "pallas")} <= kinds
    for kind in ("quantized_allreduce", "allgather"):
        assert {(kind, 4, "jnp"), (kind, 4, "pallas")} <= kinds
    assert all(r["diff"] == 0 for r in recs)


@pytest.mark.multidevice
def test_chip_smoke_four_chip_phase_tiny():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = ("import jax, chip_smoke as s; "
            "s.four_chip_phase(jax.devices(), 0, bucket_bytes=%d, "
            "qbucket_bytes=%d, arch_smoke=True); print('ALL OK')"
            % (TINY["bucket_bytes"], TINY["qbucket_bytes"]))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    assert res.returncode == 0, f"{res.stdout}\n{res.stderr}"
    assert "ALL OK" in res.stdout
    for kind in ("allreduce", "reduce_scatter", "allgather",
                 "quantized_allreduce"):
        for p in (4, 3):
            for backend in ("jnp", "pallas"):
                assert re.search(rf"kind={kind} p={p} n=8 \S+ "
                                 rf"backend={backend} ", res.stdout)
    assert "kind=broadcast_state p=4" in res.stdout


def test_chip_smoke_refuses_to_run_off_tpu():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr
