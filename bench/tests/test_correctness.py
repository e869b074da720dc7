"""``correct`` on each cell, at a size a CPU test run holds: a sound run
passes; the timed path broken underneath fails, once for each fault the
cell can have; the control (the plain reference one precision below the
stated ones, in the program's place) fails its limits."""

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

DDP, INT8 = "ddp_allreduce.25m", "int8_gradsync.4m.rankstack"


def tiny(name, bucket_bytes):
    cell = harness.load_cell(name)
    cell.config = {**cell.config, "bucket_bytes": bucket_bytes}
    cell.traffic = {**cell.traffic, "baseline_calls": 3, "sample_calls": 4}
    return cell


def broken(cell, wrap):
    """The cell with its Job's timed callable replaced by
    ``wrap(fn, job)``."""
    base = cell.entry.Job

    class Broken(base):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.fn = wrap(self.fn, self)

    cell.entry = SimpleNamespace(Job=Broken, program=cell.entry.program)
    return cell


def run(cell, seed=2**40 + 3):
    return harness.run(cell, seed, 0.2, False, time.perf_counter(),
                       require_tpu=False)


def half(p):
    return jnp.asarray((np.arange(p) < p // 2).astype(np.float32))[:, None]


@pytest.mark.parametrize("name", [DDP, INT8])
def test_sound_run_is_correct(name):
    res = run(tiny(name, 4 * 8192))
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


DDP_FAULTS = {
    # the state it should change comes back unchanged: the input
    "state_unchanged": lambda fn, job: (lambda x: x),
    "half_batch": lambda fn, job: (lambda x: 2 * fn(x * half(job.p))),
    "answer_altered": lambda fn, job: (lambda x: fn(x).at[1, 3].add(1.0)),
}

INT8_FAULTS = {
    "state_unchanged": lambda fn, job: (lambda g, e: (fn(g, e)[0], e)),
    "half_batch": lambda fn, job: (
        lambda g, e: (lambda m, ne: (2 * m, ne))(
            *fn(g * half(job.p), e * half(job.p)))),
    "answer_altered": lambda fn, job: (
        lambda g, e: (lambda m, ne: (m.at[1, 3].set(1e6), ne))(*fn(g, e))),
}


@pytest.mark.parametrize("name,fault", [(DDP, f) for f in DDP_FAULTS]
                         + [(INT8, f) for f in INT8_FAULTS])
def test_fault_in_timed_path_is_not_correct(name, fault):
    faults = DDP_FAULTS if name == DDP else INT8_FAULTS
    res = run(broken(tiny(name, 4 * 8192), faults[fault]))
    assert res["correct"] is False, res["checks"]
    assert res["failed"] > 0


@pytest.mark.parametrize("name,bucket", [(DDP, 4 * 8448), (INT8, 4 * 8448)])
def test_exchange_left_out_is_not_correct(name, bucket, monkeypatch):
    # a payload size no other test plans, so the plan is traced afresh
    # with the exchange gone
    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, axis_name, perm: x)
    res = run(tiny(name, bucket))
    assert res["correct"] is False, res["checks"]


def test_error_state_not_carried_is_not_correct():
    # the error state is dropped between calls: each call's own
    # conservation holds for the state it was given, only a chain of
    # consecutive calls shows the error that went missing
    cell = tiny(INT8, 4 * 8192)
    base = cell.entry.Job

    class Dropping(base):
        def issue(self, i):
            out = super().issue(i)
            self.state = jnp.zeros_like(self.state)
            return out

    cell.entry = SimpleNamespace(Job=Dropping, program=cell.entry.program)
    res = run(cell)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["completeness_steps"]["value"] > 0.1


def samples(name, bucket=4 * 8192, k=3, seed=11):
    """Host copies of ``k`` sampled chains of a sound closed loop."""
    cell = tiny(name, bucket)
    job = cell.entry.Job(cell.config, cell.traffic, jax.devices()[:cell.chips],
                         seed)
    chain = cell.traffic.get("chain_calls", 1)
    res = harness.Reservoir(k, seed, chain)
    harness.closed_loop(job, 0, res, calls=(k + 5) * chain)
    return cell, [job.fetch(r) for r in res.items]


@pytest.mark.parametrize("name", [DDP, INT8])
def test_control_fails_its_limits_where_the_program_passes(name):
    cell, got = samples(name)
    limits = cell.config["limits"]
    for s in got:
        sound = cell.reference.numbers(s, cell.config)
        assert all(sound[k] <= limits[k] for k in limits), sound
        ctrl = cell.reference.numbers(cell.reference.control(s, cell.config),
                                      cell.config)
        # the control fails every number the cell compares
        assert all(ctrl[k] > limits[k] for k in limits), ctrl
