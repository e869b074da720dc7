"""repro.analysis: positive sweeps + adversarial corruption injection.

The analyzer is only worth its CI minutes if it (a) passes clean on
every real artifact and (b) REJECTS corrupted ones -- a vacuous checker
passes (a) trivially.  Mirroring tests/test_verify_negative.py, every
negative case here first audits the *unmutated* artifact clean, then
injects one corruption into a COPY (cached tables are immutable and
shared process-wide; nothing here may touch the originals) and asserts
the matching pass reports the matching check id.

Corruption classes covered (each keyed to its Finding.check):
  plan pass   -- write-once, raw-send, exchange, slot-range, ks-sequence,
                 rotation, round-count, root-pin, lost-partial,
                 mutable-table, bundle-consistency, phase-layout
  kernel pass -- ww-overlap, raw-alias, alias-map, dtype-widening
  cache pass  -- mutable-cache-entry
  lint pass   -- frozen-plan, mutable-default, host-plane-jax, api-doc
"""

import dataclasses
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (
    AnalysisError,
    Report,
    audit_cache,
    audit_hier_kind,
    audit_kind,
    audit_phase,
    audit_plan,
    audit_statics,
    statics_for_kind,
)
from repro.analysis.lint import lint_api_docs, lint_repo, lint_source
from repro.analysis.planaudit import HIER_PLAN_KINDS, PLAN_KINDS
from repro.core.engine import get_bundle

from conftest import run_worker

ROOT = Path(__file__).resolve().parents[1]


def _thaw(ps, which):
    """A copy of phase static ``ps`` with slot table ``which`` writable
    (refrozen copies of the rest): mutate, refreeze, rebuild."""
    slots = []
    for i, tab in enumerate(ps.slots):
        c = tab.copy()
        if i != which:
            c.setflags(write=False)
        slots.append(c)
    return dataclasses.replace(ps, slots=tuple(slots)), slots


def _refrozen(ps, slots):
    for s in slots:
        s.setflags(write=False)
    return ps


def _bcast(p=5, n=4, root=0):
    (ps,) = statics_for_kind("broadcast", p, n, root)
    assert audit_statics((ps,)).ok, "clean broadcast static must audit ok"
    return ps


def _reduce(p=5, n=4, root=0):
    (ps,) = statics_for_kind("reduce", p, n, root)
    assert audit_statics((ps,)).ok, "clean reduce static must audit ok"
    return ps


# ------------------------------------------------------- positive sweeps


@pytest.mark.parametrize("kind", PLAN_KINDS)
@pytest.mark.parametrize("p", [2, 3, 4, 7, 8, 16, 17, 36, 64])
def test_audit_kind_clean(kind, p):
    rep = audit_kind(kind, p, n=4, root=p - 1)
    assert rep.ok, rep.summary()
    assert rep.checked > 0
    rep.raise_if_failed()  # must not raise when clean


@pytest.mark.parametrize("kind", HIER_PLAN_KINDS)
@pytest.mark.parametrize("mesh", [(2, 2), (2, 4), (6, 4), (36, 32)])
def test_audit_hier_kind_clean(kind, mesh):
    nodes, cores = mesh
    rep = audit_hier_kind(kind, nodes, cores, n_inter=4, n_intra=4)
    assert rep.ok, rep.summary()
    assert rep.checked > 0


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("kind",
                         ["broadcast", "allgather", "reduce",
                          "quantized_allreduce"])
def test_audit_host_plan_clean(backend, kind):
    from repro.core.comm import host_plan

    plan = host_plan(kind, 5, n=4, backend=backend)
    rep = audit_plan(plan)
    assert rep.ok, rep.summary()
    assert rep.checked > 1  # the plan itself plus >= 1 phase


@pytest.mark.parametrize("kind", HIER_PLAN_KINDS)
def test_audit_hier_host_plan_clean(kind):
    from repro.core.hier import hier_host_plan

    plan = hier_host_plan(kind, 2, 4, 2, 3)
    rep = audit_plan(plan)
    assert rep.ok, rep.summary()


def test_cache_audit_clean():
    get_bundle(7, 0)  # ensure the cache is non-trivial
    rep = audit_cache()
    assert rep.ok, rep.summary()
    assert rep.checked > 0


def test_lint_repo_clean():
    rep = lint_repo(ROOT)
    assert rep.ok, rep.summary()
    assert rep.checked > 30  # the whole src/repro tree was walked


def test_report_aggregation():
    a = audit_kind("broadcast", 5, 4)
    b = audit_kind("reduce", 5, 4)
    both = a + b
    assert both.checked == a.checked + b.checked
    assert both.raise_if_failed() is both  # clean -> returns self


# ------------------------------------------- plan-pass corruption classes


def test_duplicate_recv_slot_rejected():  # class 1: write-once
    ps = _bcast()
    bad, slots = _thaw(ps, 0)
    recv = slots[0]
    # rank 1's real receives are distinct; alias round t2 onto t1
    col = recv[:, 1]
    real_rounds = np.flatnonzero(col < ps.n - 1)
    assert len(real_rounds) >= 2
    recv[real_rounds[1], 1] = recv[real_rounds[0], 1]
    rep = audit_statics((_refrozen(bad, slots),))
    assert rep.has("write-once"), rep.summary()
    with pytest.raises(AnalysisError):
        rep.raise_if_failed()


def test_out_of_range_slot_rejected():  # class 2: slot-range
    ps = _bcast()
    bad, slots = _thaw(ps, 0)
    slots[0][0, 0] = ps.nslots + 3
    rep = audit_statics((_refrozen(bad, slots),))
    assert rep.has("slot-range"), rep.summary()


def test_round_count_drift_rejected():  # class 3: round-count
    ps = _bcast()
    sliced = tuple(t[:-1].copy() for t in ps.slots)
    for t in sliced:
        t.setflags(write=False)
    bad = dataclasses.replace(ps, slots=sliced, ks=ps.ks[:-1],
                              shifts=ps.shifts[:-1])
    rep = audit_statics((bad,))
    assert rep.has("round-count"), rep.summary()


def test_wrong_ks_column_rejected():  # class 4: ks-sequence
    ps = _bcast(p=8)
    bad = dataclasses.replace(ps, ks=np.ascontiguousarray(ps.ks[::-1]))
    rep = audit_statics((bad,))
    assert rep.has("ks-sequence"), rep.summary()


def test_wrong_rotation_rejected():  # class 5: rotation
    ps = _bcast()
    shifts = list(ps.shifts)
    shifts[0] = (shifts[0] + 1) % ps.p
    bad = dataclasses.replace(ps, shifts=tuple(shifts))
    rep = audit_statics((bad,))
    assert rep.has("rotation"), rep.summary()


def test_exchange_inconsistency_rejected():  # class 6: exchange
    ps = _bcast()
    bad, slots = _thaw(ps, 1)
    send = slots[1]
    # divert one real send to a different (valid-range) slot
    t, r = np.argwhere(send < ps.n - 1)[0]
    send[t, r] = (send[t, r] + 1) % (ps.n - 1)
    rep = audit_statics((_refrozen(bad, slots),))
    assert rep.has("exchange"), rep.summary()


def test_send_before_receive_rejected():  # class 7: raw-send (RAW order)
    ps = _bcast()
    bad, slots = _thaw(ps, 1)
    send = slots[1]
    r = (ps.root + 1) % ps.p
    send[0, r] = 0  # a real slot, but round 0 precedes any receive
    rep = audit_statics((_refrozen(bad, slots),))
    assert rep.has("raw-send"), rep.summary()


def test_unpinned_root_fwd_rejected():  # class 8: root-pin
    ps = _reduce()
    bad, slots = _thaw(ps, 0)
    slots[0][0, ps.root] = 0  # leak a live partial from the root
    rep = audit_statics((_refrozen(bad, slots),))
    assert rep.has("root-pin"), rep.summary()


def test_lost_partial_rejected():  # class 9: lost-partial
    ps = _reduce()
    bad, slots = _thaw(ps, 1)
    acc = slots[1]
    r = (ps.root + 1) % ps.p
    acc[-1, r] = 0  # accumulate a real partial with no later forward
    rep = audit_statics((_refrozen(bad, slots),))
    assert rep.has("lost-partial"), rep.summary()


def test_writable_table_rejected():  # class 10: mutable-table
    ps = _bcast()
    thawed = tuple(t.copy() for t in ps.slots)  # copies stay writable
    bad = dataclasses.replace(ps, slots=thawed)
    rep = audit_statics((bad,))
    assert rep.has("mutable-table"), rep.summary()
    assert not rep.has("bundle-consistency"), \
        "values were unchanged; only mutability may fire"


def test_foreign_tables_rejected():  # class 11: bundle-consistency
    ps = _bcast(p=5)
    other = _bcast(p=5, root=2)  # right shapes, wrong root's tables
    bad = dataclasses.replace(ps, slots=other.slots)
    rep = audit_statics((bad,))
    assert rep.has("bundle-consistency"), rep.summary()


class _FakeFlatPlan:
    kind = "allreduce"
    p = 5
    root = 0
    n_blocks = 4
    backend = "jnp"
    rounds = 99  # closed form is 2*(n-1) + 2*ceil(log2 p) = 12

    @property
    def statics(self):
        # reduce phase missing: broadcast only, and twice
        (b,) = statics_for_kind("broadcast", 5, 4, 0)
        return (b, b)


def test_fake_plan_layout_rejected():  # class 12: phase-layout+round-count
    rep = audit_plan(_FakeFlatPlan())
    assert rep.has("round-count"), rep.summary()
    assert rep.has("phase-layout"), rep.summary()


def test_plan_without_statics_rejected():
    class Bare:
        pass

    rep = audit_plan(Bare())
    assert rep.has("no-statics")


# ----------------------------------------- kernel-pass corruption classes


def _pack_spec(R=4, nslots=5, bs=8):
    from repro.kernels import block_pack as bp

    spec = bp.kernel_audit_spec("block_pack", R=R, nslots=nslots, bs=bs)
    from repro.analysis.kernelaudit import replay_kernel

    idx = np.arange(R, dtype=np.int32) % nslots
    assert not replay_kernel(spec, (idx,)), "clean spec must replay clean"
    return bp, spec, idx


def test_overlapping_output_blocks_rejected():  # class 13: ww-overlap
    from repro.analysis.kernelaudit import replay_kernel

    bp, spec, idx = _pack_spec()
    evil_out = dataclasses.replace(
        spec.outputs[0], index_map=lambda r, j, i: (0, 0, 0))  # every r -> row 0
    bad = dataclasses.replace(spec, outputs=(evil_out,))
    findings = replay_kernel(bad, (idx,))
    assert any(f.check == "ww-overlap" for f in findings), findings


def test_alias_read_back_rejected():  # class 14: raw-alias
    from repro.analysis.kernelaudit import replay_kernel

    bp = pytest.importorskip("repro.kernels.block_pack")
    R, nslots, bs = 4, 5, 8
    spec = bp.kernel_audit_spec("block_unpack", R=R, nslots=nslots, bs=bs)
    idx = np.zeros(R, dtype=np.int32)  # every row writes slot 0...
    # ...and the aliased input becomes LIVE and reads the previous row's
    # written block: the exact interpret/compiled divergence hazard.
    live_alias = dataclasses.replace(
        spec.inputs[1], live=None,
        index_map=lambda r, j, i: (max(r - 1, 0), i[max(r - 1, 0)], j, 0))
    bad = dataclasses.replace(spec, inputs=(spec.inputs[0], live_alias))
    findings = replay_kernel(bad, (idx,))
    assert any(f.check == "raw-alias" for f in findings), findings


def test_alias_map_mismatch_rejected():  # class 15: alias-map
    from repro.analysis.kernelaudit import replay_kernel

    bp, spec, idx = _pack_spec()
    from repro.kernels.block_pack import kernel_audit_spec

    spec = kernel_audit_spec("block_unpack", R=4, nslots=5, bs=8)
    skewed = dataclasses.replace(
        spec.inputs[1], index_map=lambda r, j, i: (r, (i[r] + 1) % 5, j, 0))
    bad = dataclasses.replace(spec, inputs=(spec.inputs[0], skewed))
    findings = replay_kernel(bad, (np.arange(4, dtype=np.int32),))
    assert any(f.check == "alias-map" for f in findings), findings


def test_dtype_drift_rejected():  # class 16: dtype-widening
    from repro.analysis.kernelaudit import audit_kernel_trace
    from repro.kernels.block_pack import kernel_audit_spec

    spec = kernel_audit_spec("block_acc_shuffle", R=3, nslots=4, bs=8)
    lying = dataclasses.replace(
        spec, out_dtypes=lambda dt: (np.dtype(np.float64), dt))
    findings = audit_kernel_trace("block_acc_shuffle", R=3, nslots=4,
                                  bs=8, spec=lying)
    assert any(f.check == "dtype-widening" for f in findings), findings


def test_kernel_registry_traces_clean():
    from repro.analysis.kernelaudit import audit_kernels

    rep = audit_kernels(ps=(3, 5), ns=(4,))
    assert rep.ok, rep.summary()
    assert rep.checked > 0


# ------------------------------------------ cache-pass corruption class


def test_writable_cache_entry_rejected():  # class 17: mutable-cache-entry
    frozen = np.zeros(3)
    frozen.setflags(write=False)
    fake_cache = {
        ("slots/test", 5, 0, 4): (frozen, np.zeros(3)),  # 2nd is writable
    }
    rep = audit_cache(fake_cache)
    assert rep.has("mutable-cache-entry"), rep.summary()
    assert rep.checked == 1


# ------------------------------------------- lint-pass corruption classes


def test_unfrozen_plan_dataclass_rejected():  # class 18: frozen-plan
    src = ("from dataclasses import dataclass\n"
           "@dataclass\n"
           "class EvilPlan:\n"
           "    x: int = 0\n")
    findings = lint_source(src, "evil.py")
    assert any(f.check == "frozen-plan" for f in findings), findings
    ok = src.replace("@dataclass", "@dataclass(frozen=True)")
    assert not lint_source(ok, "ok.py")


def test_mutable_default_rejected():  # class 19: mutable-default
    findings = lint_source("def f(xs=[]):\n    return xs\n", "evil.py")
    assert any(f.check == "mutable-default" for f in findings), findings
    findings = lint_source("def g(*, m=dict()):\n    return m\n", "evil.py")
    assert any(f.check == "mutable-default" for f in findings), findings
    assert not lint_source("def h(x=(), y=None):\n    return x\n", "ok.py")


def test_host_plane_jax_import_rejected():  # class 20: host-plane-jax
    findings = lint_source("import jax.numpy as jnp\n", "core/x.py",
                           host_plane=True)
    assert any(f.check == "host-plane-jax" for f in findings), findings
    findings = lint_source("from jax import numpy\n", "core/x.py",
                           host_plane=True)
    assert any(f.check == "host-plane-jax" for f in findings), findings
    # lazy function-local imports are the sanctioned escape hatch
    assert not lint_source("def f():\n    import jax\n    return jax\n",
                           "core/x.py", host_plane=True)
    # and non-host-plane modules may import jax freely
    assert not lint_source("import jax\n", "models/x.py", host_plane=False)


def test_kernel_interpret_default_rejected():  # class 22: kernel-interpret
    """Public kernel entry points must default interpret=None (platform
    auto-detect via resolve_interpret): a baked-in True never compiles
    the kernel on a real accelerator, a baked-in False breaks every
    host-only environment."""
    for baked in ("True", "False"):
        src = (f"def schedule_op(x, *, interpret={baked}):\n"
               f"    return x\n")
        findings = lint_source(src, "kernels/x.py", kernel_plane=True)
        assert any(f.check == "kernel-interpret" for f in findings), findings
    # interpret=None is the sanctioned default
    assert not lint_source(
        "def schedule_op(x, *, interpret=None):\n    return x\n",
        "kernels/x.py", kernel_plane=True)
    # private helpers may thread a resolved bool
    assert not lint_source(
        "def _impl(x, interpret=True):\n    return x\n",
        "kernels/x.py", kernel_plane=True)
    # non-kernel-plane modules are out of scope for this rule
    assert not lint_source(
        "def schedule_op(x, *, interpret=True):\n    return x\n",
        "train/x.py", kernel_plane=False)


def test_undocumented_symbol_rejected(tmp_path):  # class 21: api-doc
    (tmp_path / "src/repro/core").mkdir(parents=True)
    (tmp_path / "docs").mkdir()
    (tmp_path / "src/repro/core/__init__.py").write_text(
        '__all__ = ["documented_fn", "ghost_fn"]\n')
    (tmp_path / "docs/api.md").write_text("# API\n`documented_fn` only\n")
    findings = lint_api_docs(tmp_path)
    assert any(f.check == "api-doc" and "ghost_fn" in f.message
               for f in findings), findings


# ------------------------------------------------------ device coverage


@pytest.mark.multidevice
@pytest.mark.parametrize("p", [2, 4])
def test_device_plan_audit(p):
    run_worker("analysis", p, "jnp", 2)


@pytest.mark.multidevice
def test_device_plan_audit_pallas():
    run_worker("analysis", 4, "pallas", 2)


# --------------------------------------------------------------- the CLI


def test_cli_plans_lint_cache(tmp_path, capsys):
    from repro.analysis.__main__ import main

    bench = tmp_path / "bench.json"
    assert main(["--plans", "--lint", "--cache",
                 "--bench", str(bench)]) == 0
    out = capsys.readouterr().out
    assert "OK:" in out and bench.exists()
    import json

    payload = json.loads(bench.read_text())
    assert payload["total"]["findings"] == 0
    assert payload["passes"]["plans"]["checked"] > 0
