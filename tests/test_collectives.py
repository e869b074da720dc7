"""Multi-device tests for the circulant JAX collective family.

Each case runs tests/mp_worker.py in a subprocess with
``--xla_force_host_platform_device_count=p`` so the main pytest process
keeps its single-device view (required for the smoke tests).  All tests
here carry the ``multidevice`` marker (see pytest.ini); the schedule-only
fast lane runs ``pytest -q -m "not multidevice"``.  When the worker
cannot get p devices (a backend ignoring the forcing flag), it reports
SKIP and the test skips gracefully."""

import pytest

from conftest import run_worker

pytestmark = pytest.mark.multidevice


@pytest.mark.parametrize("p", [2, 5, 8])
def test_circulant_broadcast_multidevice(p):
    run_worker("broadcast", p)


@pytest.mark.parametrize("p", [2, 5, 8])
def test_circulant_allgather_multidevice(p):
    run_worker("allgather", p)


@pytest.mark.parametrize("p", [3, 8])
def test_circulant_allgatherv_multidevice(p):
    run_worker("allgatherv", p)


@pytest.mark.parametrize("p", [3, 8])
def test_restore_broadcast_multidevice(p):
    run_worker("restore", p)


@pytest.mark.parametrize("p", [4, 8])
def test_compressed_allreduce_multidevice(p):
    run_worker("compressed", p)


def test_compressed_allreduce_pallas_multidevice():
    run_worker("compressed", 4, backend="pallas")


@pytest.mark.parametrize("p", [2, 4])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_gradsync_parity_multidevice(p, backend):
    """grad_sync='compressed' vs 'auto': bounded loss-trajectory
    divergence over 20 optimizer steps (end-to-end trainer path)."""
    run_worker("gradsync", p, backend=backend)


@pytest.mark.parametrize("p", [3, 5, 8])
def test_circulant_reduce_scatter_multidevice(p):
    run_worker("reducescatter", p)


@pytest.mark.parametrize("p", [2, 5, 8])
def test_circulant_reduce_multidevice(p):
    run_worker("reduce", p)


@pytest.mark.parametrize("p", [2, 5, 8])
def test_circulant_allreduce_multidevice(p):
    run_worker("allreduce", p)


@pytest.mark.parametrize("p", [3, 8])
def test_circulant_allbroadcast_multidevice(p):
    run_worker("allbroadcast", p)


@pytest.mark.parametrize(
    "what", ["broadcast", "allgather", "allgatherv", "reduce", "allreduce"]
)
def test_collective_pallas_backend_multidevice(what):
    """The Pallas (interpret) round-step backend inside real shard_map
    collectives on a forced multi-device host mesh."""
    run_worker(what, 5, backend="pallas")


def test_reduce_scatter_reversal_property():
    """Beyond-paper: the time-reversed Algorithm-2 schedule is an exact
    reduce-scatter, checked combinatorially for many (p, n)."""
    import numpy as np

    from repro.core.schedule import (
        ceil_log2, compute_skips, recv_schedule, virtual_rounds,
    )

    rng = np.random.default_rng(0)
    for p in [2, 3, 5, 8, 13, 17, 33]:
        for n in [1, 2, 5, 9]:
            q = ceil_log2(p)
            skip = compute_skips(p)
            recv = [recv_schedule(p, r) for r in range(p)]
            x = virtual_rounds(p, n)
            X = rng.integers(0, 100, size=(p, p, n)).astype(np.int64)
            P = np.concatenate([X.copy(), np.zeros((p, p, 1), np.int64)], axis=2)

            def slot(r_, j, k, off):
                e = recv[(r_ - j) % p][k] + off
                return min(e, n - 1) if e >= 0 else None

            for i in reversed(range(x, n + q - 1 + x)):
                k = i % q
                off = q * ((i - k) // q) - x
                msgs = []
                for t in range(p):
                    payload = np.zeros((p,), np.int64)
                    for j in range(p):
                        s = slot(t, j, k, off)
                        if s is not None:
                            payload[j] = P[t, j, s]
                    msgs.append((t, (t - skip[k]) % p, payload))
                for t, dst, payload in msgs:
                    for j in range(p):
                        s = slot(t, j, k, off)
                        if s is not None:
                            P[t, j, s] = 0
                for t, dst, payload in msgs:
                    for j in range(p):
                        s = slot((dst + skip[k]) % p, j, k, off)
                        if s is not None:
                            P[dst, j, s] += payload[j]
            expect = X.sum(axis=0)
            for r in range(p):
                assert np.array_equal(P[r, r, :n], expect[r]), (p, n, r)
