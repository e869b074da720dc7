"""Tests for the two-level hierarchical collective layer (repro.core.hier).

In-process tests cover everything that needs no devices: communicator
validation, the p=1 fast path (a 1x1 mesh works in the main process),
plan-cache identity / collision / eviction-free growth across mixed
hierarchical and flat specs, the composed closed-form round counts, and
the hierarchical simulator certification grid -- including the paper's
36x32 evaluation topology on BOTH round-step backends (the acceptance
bar for this layer).

The multidevice-marked tests run ``tests/mp_worker.py hier`` in a
subprocess on forced 2x2 / 2x4 host meshes: dict/mixed-dtype pytrees
through all four hierarchical kinds on both backends, plus the
degenerate 1xp mesh equivalence with the flat collectives.
"""

import os
import sys

import numpy as np
import pytest

from conftest import run_worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def _mesh11():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("node", "core"))


# ------------------------------------------------------------- validation


def test_hier_comm_validates_axes_and_backend():
    from repro.core.hier import HierComm

    mesh = _mesh11()
    with pytest.raises(ValueError, match="axis"):
        HierComm(mesh=mesh, inter_axis="rack", intra_axis="core")
    with pytest.raises(ValueError, match="axis"):
        HierComm(mesh=mesh, inter_axis="node", intra_axis="rack")
    with pytest.raises(ValueError, match="differ"):
        HierComm(mesh=mesh, inter_axis="node", intra_axis="node")
    with pytest.raises(ValueError, match="backend"):
        HierComm(mesh=mesh, inter_axis="node", intra_axis="core",
                 backend="cuda")


def test_hier_plan_validates_arguments():
    from repro.core.hier import get_hier_comm

    hc = get_hier_comm(_mesh11(), "node", "core")
    x = {"a": np.zeros((1, 8), np.float32)}
    with pytest.raises(ValueError, match="kind"):
        hc.plan("gossip", x)
    with pytest.raises(ValueError, match="root"):
        hc.plan("allgather", x, root=1)
    with pytest.raises(ValueError, match="op"):
        hc.plan("broadcast", x, op="max")
    with pytest.raises(ValueError, match="root"):
        hc.plan("broadcast", x, root=7)  # out of [0, nodes*cores)


def test_hier_rounds_closed_form_and_validation():
    from repro.core.hier import hier_rounds
    from repro.core.schedule import num_rounds

    assert hier_rounds("broadcast", 36, 32, 4, 3) == (
        num_rounds(36, 4) + num_rounds(32, 3))
    assert hier_rounds("allreduce", 36, 32, 4, 3) == 2 * (
        num_rounds(36, 4) + num_rounds(32, 3))
    # the family alias canonicalizes
    assert hier_rounds("allbroadcast", 6, 4, 2, 2) == hier_rounds(
        "allgather", 6, 4, 2, 2)
    # degenerate levels contribute zero rounds
    assert hier_rounds("broadcast", 1, 8, 5, 3) == num_rounds(8, 3)
    assert hier_rounds("reduce", 8, 1, 3, 5) == num_rounds(8, 3)
    with pytest.raises(ValueError, match="kind"):
        hier_rounds("gossip", 2, 2, 1, 1)


def test_hier_p1_fast_path_identity_pytree():
    import jax

    from repro.core.hier import get_hier_comm

    hc = get_hier_comm(_mesh11(), "node", "core")
    state = {"w": np.arange(12, dtype=np.float32).reshape(1, 12),
             "b": (np.arange(5, dtype=np.int32).reshape(1, 5),)}
    for kind in ("broadcast", "reduce", "allreduce", "allgather"):
        plan = hc.plan(kind, state)
        assert plan.p == 1 and plan.rounds == 0
        out = plan(state)
        assert jax.tree.structure(out) == jax.tree.structure(state)
        np.testing.assert_array_equal(out["w"], state["w"])
    # mismatched payloads are rejected by the shared validator
    plan = hc.plan("broadcast", state)
    with pytest.raises(ValueError, match="tree"):
        plan({"x": state["w"]})
    with pytest.raises(ValueError, match="leaf"):
        plan({"w": state["w"].astype(np.float64), "b": state["b"]})


# ------------------------------------ plan-cache identity / growth audit


def test_hier_plan_cache_identity_and_eviction_free_growth():
    """Eviction-free growth across mixed hier+flat specs: repeated
    planning never grows the cache (pure hits), distinct specs add
    exactly their own entries, and nothing is ever evicted."""
    from repro.core.comm import host_plan
    from repro.core.engine import plan_cache_info, plan_cache_keys
    from repro.core.hier import get_hier_comm, hier_host_plan

    hc = get_hier_comm(_mesh11(), "node", "core")
    x = {"a": np.zeros((1, 8), np.float32)}
    p1 = hc.plan("broadcast", x, n_inter=2, n_intra=2)
    keys_before = set(plan_cache_keys())
    info_before = plan_cache_info()
    # pure replanning: identity, zero growth
    for _ in range(5):
        assert hc.plan("broadcast", x, n_inter=2, n_intra=2) is p1
    assert plan_cache_info()["size"] == info_before["size"]
    assert plan_cache_info()["hits"] >= info_before["hits"] + 5
    # the alias kind canonicalizes onto the same entry
    assert hc.plan("allbroadcast", x) is hc.plan("allgather", x)
    # mixed hier + flat specs with the same numeric parameters coexist:
    # namespaced keys cannot collide, so each adds its own entries and
    # evicts nothing
    hp_flat = host_plan("broadcast", 6, 2)
    hp_hier = hier_host_plan("broadcast", 6, 2, 2, 2)
    assert hp_flat is not hp_hier
    assert hp_flat is host_plan("broadcast", 6, 2)
    assert hp_hier is hier_host_plan("broadcast", 6, 2, 2, 2)
    keys_after = set(plan_cache_keys())
    assert keys_before <= keys_after, "plan cache evicted entries"
    assert len(keys_after) == plan_cache_info()["size"]
    # every key is namespaced by a distinct leading tag
    tags = {k[0] for k in keys_after if isinstance(k, tuple)}
    assert tags <= {"commplan", "hierplan", "hostplan", "hierhostplan",
                    "comm", "hiercomm", "qsync", "slots/bcast",
                    "slots/reduce", "slots/scatter"}, tags


def test_hier_and_flat_host_plans_do_not_collide():
    """A hier host plan over (p, 1) and the flat host plan over p share
    per-level flat entries but keep distinct top-level identities."""
    from repro.core.comm import host_plan
    from repro.core.hier import hier_host_plan

    flat = host_plan("broadcast", 9, 3)
    hier = hier_host_plan("broadcast", 9, 1, 3, 1)
    assert flat is not hier
    # the hier plan's inter level IS the cached flat plan (shared entry)
    assert hier.inter is flat
    vals = np.arange(6, dtype=np.int64)
    got = hier.run(vals)
    assert got.shape == (9, 1, 6)
    for j in range(9):
        np.testing.assert_array_equal(got[j, 0], vals)


def test_hier_comm_cached_identity():
    from repro.core.costmodel import CommModel
    from repro.core.hier import get_hier_comm

    mesh = _mesh11()
    h1 = get_hier_comm(mesh, "node", "core")
    assert h1 is get_hier_comm(mesh, "node", "core")
    assert h1 is not get_hier_comm(mesh, "node", "core", backend="pallas")
    assert h1 is not get_hier_comm(
        mesh, "node", "core", inter_model=CommModel(alpha=5e-5))


def test_optimal_hier_blocks_per_level_decoupling():
    from repro.core.costmodel import (
        CommModel,
        hier_cost,
        optimal_hier_blocks,
        optimal_num_blocks_bcast,
    )

    slow = CommModel(alpha=2e-5, beta=1e-9)    # inter-node: latency-heavy
    fast = CommModel(alpha=5e-7, beta=2e-11)   # intra-node
    m = 1 << 22
    nN, nC = optimal_hier_blocks(36, 32, m, m, slow, fast)
    assert nN == optimal_num_blocks_bcast(36, m, slow)
    assert nC == optimal_num_blocks_bcast(32, m, fast)
    # the two-level cost at the optimum beats obviously bad block counts
    best = hier_cost("broadcast", 36, 32, m, m, nN, nC, slow, fast)
    assert best <= hier_cost("broadcast", 36, 32, m, m, 1, 1, slow, fast)
    assert best <= hier_cost("broadcast", 36, 32, m, m, m, m, slow, fast)
    with pytest.raises(ValueError, match="kind"):
        optimal_hier_blocks(2, 2, 8, 8, kind="gossip")
    with pytest.raises(ValueError, match="kind"):
        hier_cost("gossip", 2, 2, 8, 8, 1, 1)


# ------------------------------------------- simulator certification grid


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_simulate_hier_certification_grid(backend):
    """Hier broadcast/reduce/allreduce certify bit-exact against the
    composed host data plane over a (nodes x cores) grid, both
    backends, with composed round counts asserted internally."""
    from repro.core import (
        simulate_hier_allreduce,
        simulate_hier_broadcast,
        simulate_hier_reduce,
    )

    for nodes, cores in [(1, 1), (1, 5), (5, 1), (2, 3), (4, 4), (3, 8)]:
        for nN, nC in [(1, 2), (2, 3)]:
            root = (nodes * cores) // 2
            simulate_hier_broadcast(nodes, cores, nN, nC, root=root,
                                    backend=backend)
            simulate_hier_reduce(nodes, cores, nN, nC, root=root,
                                 backend=backend)
        simulate_hier_allreduce(nodes, cores, 2, 2, backend=backend)
    simulate_hier_reduce(3, 4, 2, 2, op="max", backend=backend)
    simulate_hier_allreduce(2, 4, 1, 2, op="max", backend=backend)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_simulate_hier_36x32_paper_topology(backend):
    """The paper's full 36x32 evaluation topology certifies on both
    backends: composed optimum round counts and bit-exact data planes
    (1152 simulated ranks -- far beyond any local device mesh)."""
    from repro.core import (
        simulate_hier_allreduce,
        simulate_hier_broadcast,
        simulate_hier_reduce,
    )
    from repro.core.schedule import num_rounds

    r = simulate_hier_broadcast(36, 32, 3, 2, root=35 * 32 + 7,
                                backend=backend)
    assert (r.rounds, r.rounds_inter, r.rounds_intra) == (
        r.optimal_rounds, num_rounds(36, 3), num_rounds(32, 2))
    r = simulate_hier_reduce(36, 32, 2, 2, root=100, backend=backend)
    assert r.rounds == r.optimal_rounds
    r = simulate_hier_allreduce(36, 32, 2, 1, backend=backend)
    assert r.rounds == r.optimal_rounds


def test_simulate_hier_float_sum_and_custom_values():
    """Float sums certify against the schedule-order data plane; int
    payload shape/divisibility validation raises."""
    from repro.core import simulate_hier_reduce

    rng = np.random.default_rng(3)
    vals = rng.normal(size=(3, 4, 12)).astype(np.float64)
    r = simulate_hier_reduce(3, 4, 2, 3, values=vals, backend="jnp")
    np.testing.assert_allclose(
        np.asarray(r.buffers[0]), vals.reshape(12, 12).sum(axis=0),
        rtol=1e-12)
    with pytest.raises(AssertionError, match="divide"):
        simulate_hier_reduce(2, 2, 2, 3, values=np.zeros((2, 2, 7)))


def test_hier_host_plan_validates():
    from repro.core.hier import hier_host_plan

    with pytest.raises(ValueError, match="kind"):
        hier_host_plan("gossip", 2, 2, 1, 1)
    with pytest.raises(ValueError, match="root"):
        hier_host_plan("broadcast", 2, 2, 1, 1, root=4)


# --------------------------------------------------- multidevice grid


@pytest.mark.multidevice
@pytest.mark.parametrize("nodes,cores", [(2, 2), (2, 4)])
def test_hier_pytree_multidevice(nodes, cores):
    """Dict/mixed-dtype pytrees through all four hierarchical kinds on
    a real (forced) 2D device mesh, jnp data plane."""
    run_worker("hier", nodes * cores, "jnp", nodes)


@pytest.mark.multidevice
def test_hier_pytree_multidevice_pallas():
    """The same grid through the fused Pallas (interpret) data plane."""
    run_worker("hier", 4, "pallas", 2)
