"""The program's names for its work (``repro.core.tracing``): the host
spans a plan call writes, the device scopes that reach the compiled
HLO, and the plans' static permute and wire-byte counters."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_worker
from repro.core import tracing
from repro.core.roundstep import RoundStep, get_round_step

SCOPES = {k: v for k, v in vars(tracing).items()
          if k.isupper() and isinstance(v, str)}
ROUNDSTEP_METHODS = ("pack", "unpack", "shuffle", "shuffle_staged",
                     "acc_shuffle", "acc_shuffle_staged", "qacc_shuffle")


def test_names_are_distinct_and_prefixed():
    names = list(SCOPES.values())
    assert len(set(names)) == len(names)
    for name in names:
        assert re.fullmatch(r"(circulant|gradsync|roundstep)\.[a-z_]+", name)
    steps = {v.split(".", 1)[1] for v in names if v.startswith("roundstep.")}
    assert steps == set(ROUNDSTEP_METHODS)
    assert all(callable(getattr(RoundStep, m)) for m in steps)


def _step_args(step, method):
    R, S = 2, 5
    slot = step.slot_shape(256, jnp.float32, 256)
    buf = jnp.ones((R, S) + slot, jnp.float32)
    msg = jnp.ones((R,) + slot, jnp.float32)
    idx = jnp.array([1, 2], jnp.int32)
    return {
        "pack": (buf, idx),
        "unpack": (buf, msg, idx),
        "shuffle": (buf, msg, idx, idx),
        "shuffle_staged": (buf, msg, msg, idx, idx),
        "acc_shuffle": (buf, msg, idx, idx),
        "acc_shuffle_staged": (buf, msg, msg, idx, idx),
        "qacc_shuffle": (buf, buf, msg.astype(jnp.int8),
                         jnp.ones((R, int(np.prod(slot)) // 256),
                                  jnp.float32), idx, idx),
    }[method]


@pytest.mark.parametrize("method", ROUNDSTEP_METHODS)
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_round_step_scope_reaches_compiled_hlo(backend, method):
    step = get_round_step(backend)
    fn = jax.jit(lambda *a: getattr(step, method)(*a))
    text = fn.lower(*_step_args(step, method)).compile().as_text()
    assert f"roundstep.{method}" in text
    ops = re.findall(r'op_name="([^"]*)"', text)
    assert ops and any(f"roundstep.{method}" in o for o in ops)


def test_one_rank_plan_counts_nothing():
    from jax.sharding import Mesh

    from repro.core.comm import CirculantComm

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    plan = CirculantComm(mesh, "x").plan("allreduce", jnp.ones((1, 64)))
    assert (plan.rounds, plan.permutes, plan.wire_bytes) == (0, 0, 0)
    assert "permutes=0 wire_bytes=0" in plan.describe()


def test_one_rank_grad_sync_counts_nothing():
    from repro.optim.compression import grad_sync_counters, make_bucket_spec

    spec = make_bucket_spec(jnp.ones((4096,)), 4096)
    got = grad_sync_counters(spec, 1)
    assert (got.rounds, got.permutes, got.wire_bytes,
            got.scales_wire_bytes) == (0, 0, 0, 0)


def test_grad_sync_counters_follow_the_schedule():
    """Two permutes a round for each bucket, the int8 blocks and their
    scales; at the 25 MiB DDP bucket the cost model's 11 blocks give 24
    rounds, and the scales are 1.5% of the wire."""
    from repro.optim.compression import grad_sync_counters, make_bucket_spec

    ddp = make_bucket_spec(jax.ShapeDtypeStruct((26214400 // 4,),
                                                jnp.float32), 26214400)
    got = grad_sync_counters(ddp, 4)
    assert (got.n_blocks, got.rounds, got.permutes) == (11, 24, 48)
    assert got.scales_wire_bytes == 24 * 2336 * 4     # 8 pad rows a block
    assert (got.wire_bytes, got.tiled_qslots) == (14576640, 1)
    assert 0.015 < got.scales_wire_bytes / got.wire_bytes < 0.016
    two = make_bucket_spec({"a": jnp.ones((2048,)), "b": jnp.ones((1024,))},
                           4 * 2048)
    assert two.num_buckets == 2
    got2 = grad_sync_counters(two, 4, n_blocks=2)
    assert got2.permutes == 2 * 2 * got2.rounds


@pytest.mark.multidevice
def test_plan_calls_write_spans_and_counters_match_hlo():
    """On 4 host devices: one circulant.call per plan call with one
    validate and one execute inside (flat, quantized and hierarchical
    plans), and for every kind, both backends and hierarchical plans,
    permutes / wire_bytes equal the compiled HLO's collective-permutes
    and their bytes; only the quantized plan's HLO names
    ``circulant.scales``; the trainer's sync path's counters
    (``grad_sync_counters``) equal its HLO's permutes and bytes, and
    the scales' bytes those of the permutes under that scope."""
    run_worker("tracing", 4)
