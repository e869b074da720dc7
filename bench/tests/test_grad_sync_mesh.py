"""``correct`` on the ``grad_sync`` entry's cells, at a size a CPU test run
holds (four host devices stand in for the four chips): a sound run of
each passes; the one-rank-per-chip path broken underneath fails, once
for each fault it can have; the control (the plain reference one
precision below the stated ones, in the program's place) fails its
limits where the program passes."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench.tests.test_correctness import broken, run, samples, tiny

MESH, STACK = "int8_gradsync.25m", "int8_gradsync.25m.rankstack"
BUCKET = 4 * 8192


@pytest.mark.parametrize("name", [MESH, STACK])
def test_sound_run_is_correct(name):
    res = run(tiny(name, BUCKET))
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_mesh_cell_runs_one_rank_per_chip():
    cell = tiny(MESH, BUCKET)
    job = cell.entry.Job(cell.config, cell.traffic, jax.devices()[:4], 1)
    assert len(job.ring[0].sharding.device_set) == 4
    assert job.least_ici_bytes == 2 * 3 / 4 * (8192 + 8192 // 256 * 4)
    assert "layout=mesh" in job.describe()


FAULTS = {
    "state_unchanged": lambda fn, job: (lambda g, e: (fn(g, e)[0], e)),
    "answer_altered": lambda fn, job: (
        lambda g, e: (lambda m, ne: (m.at[1, 3].set(1e6), ne))(*fn(g, e))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_timed_path_is_not_correct(fault):
    res = run(broken(tiny(MESH, BUCKET), FAULTS[fault]))
    assert res["correct"] is False, res["checks"]
    assert res["failed"] > 0


def test_exchange_left_out_is_not_correct(monkeypatch):
    # a payload size no other test plans, so the plan is traced afresh
    # with the exchange gone
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)
    res = run(tiny(MESH, 4 * 8448))
    assert res["correct"] is False, res["checks"]


def test_error_state_not_carried_is_not_correct():
    cell = tiny(MESH, BUCKET)
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


@pytest.mark.parametrize("name", [MESH, STACK])
def test_control_fails_its_limits_where_the_program_passes(name):
    cell, got = samples(name, BUCKET)
    limits = cell.config["limits"]
    for s in got:
        sound = cell.reference.numbers(s, cell.config)
        assert all(sound[k] <= limits[k] for k in limits), sound
        ctrl = cell.reference.numbers(cell.reference.control(s, cell.config),
                                      cell.config)
        assert all(ctrl[k] > limits[k] for k in limits), ctrl
