"""Planner edge cases: ``select_plan`` block selection, ``PipelinePlan``
construction/validation/serialization, and the config <-> plan round trip.
"""
import json

import pytest

from repro.core.ozaki import OzakiConfig
from repro.core.tuning import (BATCH_LAYOUTS, FUSION_MODES, PipelinePlan,
                               TilePlan, VMEM_BUDGET, apply_pipeline_plan,
                               diagonal_groups, hbm_pass_model, plan_for,
                               select_pipeline_plan, select_plan)
from repro.kernels.launch import LANE, SUBLANE_F32, SUBLANE_I8


# ----------------------------------------------------------------------------
# select_plan edge cases
# ----------------------------------------------------------------------------

def test_select_plan_tiny_k():
    """k=1: blocks floor at their alignment minima, splits stay sane."""
    plan = select_plan(8, 8, 1)
    assert plan.bm == SUBLANE_I8 and plan.bn == LANE and plan.bk == LANE
    assert plan.split_bk == LANE and plan.accum_bn == LANE
    assert plan.num_splits >= 1
    assert plan.concat_k          # short reduction -> one concatenated GEMM


def test_select_plan_k1_batched_disables_concat():
    """A stacked batch disables concat_k even for launch-bound k."""
    assert select_plan(16, 16, 1, batch=1).concat_k
    assert not select_plan(16, 16, 1, batch=4).concat_k


def test_select_plan_non_pow2_mn():
    """Non-pow2 m/n: power-of-two blocks within the aligned extents."""
    plan = select_plan(100, 130, 530)
    for b in (plan.bm, plan.bn, plan.bk, plan.accum_bm, plan.accum_bn):
        assert b & (b - 1) == 0, b
    assert plan.bm <= 128           # align_up(100, 32) = 128
    assert plan.bn <= 256
    assert plan.bm * plan.bk + plan.bn * plan.bk + \
        4 * plan.bm * plan.bn <= VMEM_BUDGET


def test_select_plan_vmem_pressure_shrinks_bk_first():
    tight = select_plan(4096, 4096, 8192, vmem_budget=VMEM_BUDGET // 8)
    default = select_plan(4096, 4096, 8192)
    assert tight.bk <= default.bk
    assert tight.bm * tight.bk + tight.bn * tight.bk + \
        4 * tight.bm * tight.bn <= VMEM_BUDGET // 8


# ----------------------------------------------------------------------------
# PipelinePlan construction / validation
# ----------------------------------------------------------------------------

def test_select_pipeline_plan_layouts():
    none = select_pipeline_plan(64, 64, 256)
    rows = select_pipeline_plan(8, 64, 256, batch=32, broadcast_weights=True)
    grid = select_pipeline_plan(8, 64, 256, batch=32)
    assert none.batch_layout == "none" and none.fusion == "epilogue"
    assert rows.batch_layout == "rows" and rows.fusion == "epilogue"
    # the batch-grid epilogue kernel keeps stacked batches epilogue-fused
    assert grid.batch_layout == "grid" and grid.fusion == "epilogue"
    # rows layout sizes tiles for the folded batch*m row extent
    assert rows.tile.bm >= none.tile.bm or rows.tile.bm == 256


def test_pipeline_plan_validation():
    with pytest.raises(ValueError, match="fusion"):
        PipelinePlan(fusion="bogus")
    with pytest.raises(ValueError, match="batch_layout"):
        PipelinePlan(batch_layout="bogus")
    with pytest.raises(ValueError, match="accum"):
        PipelinePlan(accum="f32")
    with pytest.raises(ValueError, match="pair_policy"):
        PipelinePlan(pair_policy="bogus")
    with pytest.raises(ValueError, match="budget"):
        PipelinePlan(pair_policy="budget:0")
    # epilogue + grid is a VALID plan since the batch-grid epilogue kernel
    plan = PipelinePlan(backend="pallas_fused", fusion="epilogue",
                        batch_layout="grid")
    assert plan.fusion == "epilogue"
    # so is streaming + grid (the batch-grid streaming kernel)
    plan = PipelinePlan(backend="pallas_fused", fusion="streaming",
                        batch_layout="grid")
    assert plan.fusion == "streaming"
    assert set(FUSION_MODES) == {"none", "stages", "epilogue", "streaming"}
    assert set(BATCH_LAYOUTS) == {"none", "rows", "grid"}


def test_plan_for_reflects_config():
    cfg = OzakiConfig(num_splits=11, accum="df32", backend="pallas_fused",
                      fuse_epilogue=True, shard_axis="model",
                      interpret=True)
    plan = plan_for(cfg)
    assert plan.num_splits == 11 and plan.accum == "df32"
    assert plan.fusion == "epilogue" and plan.shard_axis == "model"
    # grid layout keeps epilogue fusion (batch-grid epilogue kernel)
    assert plan_for(cfg, batch_layout="grid").fusion == "epilogue"
    # non-fused backends never fuse
    assert plan_for(OzakiConfig(backend="xla")).fusion == "none"
    assert plan_for(OzakiConfig(backend="pallas",
                                fuse_epilogue=True)).fusion == "none"
    # streaming wins the fusion slot on the fused backend, any layout
    scfg = OzakiConfig(backend="pallas_fused", streaming=True)
    assert plan_for(scfg).fusion == "streaming"
    assert plan_for(scfg, batch_layout="grid").fusion == "streaming"
    assert plan_for(OzakiConfig(backend="pallas",
                                streaming=True)).fusion == "none"


def test_plan_for_keeps_explicit_tile_blocks():
    tile = select_plan(40, 24, 200, num_splits=9)
    cfg = OzakiConfig(num_splits=5, tile=tile)   # schedule from cfg wins
    plan = plan_for(cfg)
    assert plan.tile is tile
    assert plan.num_splits == 5


def test_apply_pipeline_plan_roundtrip():
    plan = select_pipeline_plan(64, 32, 512, accum="df32",
                                shard_axis="model")
    cfg = apply_pipeline_plan(OzakiConfig(), plan)
    assert cfg.backend == "pallas_fused" and cfg.accum == "df32"
    assert cfg.fuse_epilogue and cfg.shard_axis == "model"
    assert cfg.tile == plan.tile
    assert plan_for(cfg) == plan


# ----------------------------------------------------------------------------
# Serialization round trip (deployment plan caches)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("plan", [
    PipelinePlan(),
    PipelinePlan(pair_policy="diagonal"),
    PipelinePlan(pair_policy="budget:7"),
    select_pipeline_plan(64, 64, 256),
    select_pipeline_plan(64, 64, 256, fast_mode=True),
    select_pipeline_plan(64, 64, 256, target_error=1e-8, fast_mode=True),
    select_pipeline_plan(8, 64, 7, batch=32, broadcast_weights=True,
                         accum="df32", shard_axis="model"),
    select_pipeline_plan(9, 65, 129, batch=3, backend="pallas",
                         fuse_epilogue=False, interpret=False),
    select_pipeline_plan(64, 64, 256, streaming=True),
    select_pipeline_plan(8, 64, 256, batch=32, streaming=True),
])
def test_pipeline_plan_json_roundtrip(plan):
    wire = json.dumps(plan.to_dict())
    back = PipelinePlan.from_dict(json.loads(wire))
    assert back == plan
    assert isinstance(back.tile, TilePlan)


def test_pipeline_plan_from_dict_without_pair_policy():
    """Plans serialized before the pair_policy field (PR 3 caches) load
    with the full schedule — cache files stay forward-compatible."""
    d = PipelinePlan().to_dict()
    d.pop("pair_policy")
    assert PipelinePlan.from_dict(d).pair_policy == "full"


def test_select_pipeline_plan_accuracy_knobs():
    full = select_pipeline_plan(64, 64, 128)
    fast = select_pipeline_plan(64, 64, 128, fast_mode=True)
    assert fast.pair_policy == "diagonal"
    assert fast.num_gemms < full.num_gemms
    targeted = select_pipeline_plan(64, 64, 128, target_error=1e-8,
                                    fast_mode=True)
    assert targeted.num_splits < full.num_splits     # reduced, not raised
    assert targeted.pair_policy.startswith("budget:")
    # apply_pipeline_plan carries the policy into the config and back
    cfg = apply_pipeline_plan(OzakiConfig(), targeted)
    assert cfg.pair_policy == targeted.pair_policy
    assert plan_for(cfg) == targeted


def test_streaming_plan_config_roundtrip():
    """streaming plan <-> OzakiConfig survives apply/plan_for round trip."""
    plan = select_pipeline_plan(64, 32, 512, streaming=True)
    assert plan.fusion == "streaming"
    cfg = apply_pipeline_plan(OzakiConfig(), plan)
    assert cfg.streaming and not cfg.fuse_epilogue
    assert cfg.backend == "pallas_fused"
    assert plan_for(cfg) == plan


def test_diagonal_groups_pair_budget():
    full = diagonal_groups(5)
    assert sum(len(p) for _, p in full) == 15
    cut = diagonal_groups(5, pair_budget=7)
    assert sum(len(p) for _, p in cut) == 7
    # truncation keeps the significance-ascending prefix; the partial
    # diagonal keeps its leading pairs
    assert [t for t, _ in cut] == [0, 1, 2, 3]
    assert cut[-1][1] == full[3][1][:1]
    assert diagonal_groups(5, pair_budget=1) == [(0, [(0, 0)])]


# ----------------------------------------------------------------------------
# HBM pass model: streaming < epilogue < stage-fused < unfused, every s
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("s", [5, 9, 13])
def test_hbm_pass_model_epilogue_strictly_fewer(s):
    unfused = hbm_pass_model(s, fused=False)
    stages = hbm_pass_model(s, fused=True)
    epilogue = hbm_pass_model(s, fused=True, fuse_epilogue=True)
    assert epilogue["total"] < stages["total"] < unfused["total"]
    assert epilogue["split"] == stages["split"] == 1
    assert epilogue["accum"] == 2 * s       # read C + write C per group
    # every mode pays the slice-stack traffic (the line item the model
    # used to hide): s slice writes + one read per kept pair per operand
    kept = s * (s + 1) // 2
    assert unfused["slices"] == stages["slices"] == \
        epilogue["slices"] == s + kept


@pytest.mark.parametrize("s", [5, 9, 13])
@pytest.mark.parametrize("pair_policy", ["full", "diagonal", "budget:6"])
def test_hbm_pass_model_streaming_strictly_fewer(s, pair_policy):
    """ISSUE 6 acceptance: streaming beats EVERY non-streaming mode on
    total passes once the slices line item is charged, and models the
    slice stack as never touching HBM."""
    streaming = hbm_pass_model(s, fusion="streaming",
                               pair_policy=pair_policy)
    assert streaming["slices"] == 0
    for kw in (dict(fused=False), dict(fused=True),
               dict(fused=True, fuse_epilogue=True)):
        other = hbm_pass_model(s, pair_policy=pair_policy, **kw)
        assert streaming["total"] < other["total"], (s, pair_policy, kw)


# regression pins for every (fusion mode, batch layout) combination at
# s=9: per-element counts are layout-invariant (the "rows" fold and the
# batch-grid kernels run the identical per-element pipeline — including
# the batch-grid EPILOGUE and STREAMING kernels, which remove the
# modeled 3-vs-2 passes per group the old stage-fused downgrade cost
# stacked batches), and scale linearly with the batch size. Columns:
# (split, slices, accum, total); streaming re-reads operands per group
# (split=s) but its int8 slice stack never touches HBM (slices=0).
_FUSIONS = {"none": dict(fused=False),
            "stages": dict(fused=True),
            "epilogue": dict(fused=True, fuse_epilogue=True),
            "streaming": dict(fusion="streaming")}
_PINNED_S9 = {"none": (9, 54, 45, 108), "stages": (1, 54, 27, 82),
              "epilogue": (1, 54, 18, 73), "streaming": (9, 0, 18, 27)}


@pytest.mark.parametrize("layout,batch", [("none", 1), ("rows", 1),
                                          ("grid", 1), ("rows", 4),
                                          ("grid", 4)])
@pytest.mark.parametrize("fusion", sorted(_FUSIONS))
def test_hbm_pass_model_matrix_pinned(fusion, layout, batch):
    got = hbm_pass_model(9, batch=batch, batch_layout=layout,
                         **_FUSIONS[fusion])
    split, slices, accum, total = (batch * x for x in _PINNED_S9[fusion])
    assert got == {"split": split, "slices": slices, "residues": 0,
                   "accum": accum,
                   "total": total}, (fusion, layout, batch, got)


# ----------------------------------------------------------------------------
# HBM pass model, Scheme II: the residues line item + the fused-CRT win
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("s,ell", [(7, 15), (9, 15), (9, 21)])
def test_hbm_pass_model_scheme2_epilogue_strictly_fewer(s, ell):
    """ISSUE 9 acceptance: the fused-CRT epilogue is strictly fewer
    modeled passes than every unfused Scheme II mode — the saved traffic
    is exactly the (ell, m, n) int32 residue products' round-trip."""
    kw = dict(scheme="ozaki2_fp64", num_moduli=ell)
    unfused = hbm_pass_model(s, fused=False, **kw)
    stages = hbm_pass_model(s, fused=True, **kw)
    epi = hbm_pass_model(s, fused=True, fuse_epilogue=True, **kw)
    assert epi["total"] < stages["total"] < unfused["total"]
    assert stages["accum"] - epi["accum"] == 2 * ell
    # every mode pays the residue-plane traffic; the slice stack is read
    # once by the extraction, never per pair
    for got in (unfused, stages, epi):
        assert got["residues"] == 2 * ell and got["slices"] == 2 * s


def test_hbm_pass_model_scheme2_pinned():
    # s=9, ell=15 columns: (split, slices, residues, accum, total)
    pins = {"none": (9, 18, 30, 31, 88), "stages": (1, 18, 30, 31, 80),
            "epilogue": (1, 18, 30, 1, 50)}
    for fusion, (split, slices, residues, accum, total) in pins.items():
        got = hbm_pass_model(9, fusion=fusion, scheme="ozaki2_fp64",
                             num_moduli=15)
        assert got == {"split": split, "slices": slices,
                       "residues": residues, "accum": accum,
                       "total": total}, (fusion, got)
    b = hbm_pass_model(9, fusion="epilogue", scheme="ozaki2_fp64",
                       num_moduli=15, batch=4, batch_layout="grid")
    assert b["total"] == 4 * 50


def test_hbm_pass_model_scheme2_validation():
    with pytest.raises(ValueError, match="num_moduli"):
        hbm_pass_model(9, scheme="ozaki2_fp64")
    with pytest.raises(ValueError, match="streaming"):
        hbm_pass_model(9, fusion="streaming", scheme="ozaki2_fp64",
                       num_moduli=15)
    with pytest.raises(ValueError, match="pair"):
        hbm_pass_model(9, scheme="ozaki2_fp64", num_moduli=15,
                       pair_policy="diagonal")
    with pytest.raises(ValueError, match="scheme"):
        hbm_pass_model(9, scheme="bogus")


def test_hbm_pass_model_batched_epilogue_closes_fusion_gap():
    """The batched-epilogue claim in one number: 3 -> 2 passes per
    accumulation group on the stacked-batch path."""
    stages = hbm_pass_model(9, fused=True, batch=4, batch_layout="grid")
    epi = hbm_pass_model(9, fused=True, fuse_epilogue=True, batch=4,
                         batch_layout="grid")
    assert stages["accum"] == 3 * 9 * 4 and epi["accum"] == 2 * 9 * 4


def test_hbm_pass_model_pair_policy():
    """Pair truncation drops whole accumulation groups (fused diagonals)
    or individual pair passes (paper-faithful schedule)."""
    full = hbm_pass_model(9, fused=True, fuse_epilogue=True)
    diag = hbm_pass_model(9, fused=True, fuse_epilogue=True,
                          pair_policy="diagonal")
    assert diag["accum"] == 2 * 8 and full["accum"] == 2 * 9
    unfused_budget = hbm_pass_model(9, fused=False, fuse_diagonals=False,
                                    pair_policy="budget:10")
    assert unfused_budget["accum"] == 10 * 5


def test_hbm_pass_model_validates_batch_layout():
    with pytest.raises(ValueError, match="batch_layout"):
        hbm_pass_model(9, fused=True, batch_layout="bogus")
    with pytest.raises(ValueError, match="batch"):
        hbm_pass_model(9, fused=True, batch=0)
    with pytest.raises(ValueError, match="requires"):
        hbm_pass_model(9, fused=True, batch=2, batch_layout="none")


# ----------------------------------------------------------------------------
# comm_bytes_model: the transport-layer companion to hbm_pass_model
# ----------------------------------------------------------------------------

def test_comm_bytes_model_int8_kshard_wins_6x():
    """Acceptance bar: at the paper's s=9 on a tall-k shape, the int8
    k-shard transport moves >= 6x fewer link bytes per device than the
    GSPMD f64-operand-gather baseline (and reduce-scatter doubles the
    win again by leaving C column-sharded)."""
    from repro.core.tuning import comm_bytes_model
    kw = dict(num_splits=9, world=8, layout="kshard")
    f64 = comm_bytes_model(256, 256, 8192, comm="f64", **kw)
    for sched in ("psum", "overlap"):
        i8 = comm_bytes_model(256, 256, 8192, comm="int8", schedule=sched,
                              **kw)
        assert f64["total"] / i8["total"] >= 6.0, (sched, i8)
        assert i8["operands"] == 0          # no f64 word ever on a link
    rs = comm_bytes_model(256, 256, 8192, comm="int8",
                          schedule="reduce_scatter", **kw)
    assert f64["total"] / rs["total"] >= 12.0
    assert rs["partials"] * 2 == comm_bytes_model(
        256, 256, 8192, comm="int8", schedule="psum", **kw)["partials"]


def test_comm_bytes_model_mnshard_honest_about_s():
    """m/n-shard gathers the slice stack at s bytes/element vs f64's 8:
    the model must show int8 winning for s < 8 and losing for s > 8."""
    from repro.core.tuning import comm_bytes_model
    kw = dict(world=8, layout="mnshard")
    for s, wins in ((5, True), (9, False)):
        f64 = comm_bytes_model(256, 256, 4096, num_splits=s, comm="f64",
                               **kw)
        i8 = comm_bytes_model(256, 256, 4096, num_splits=s, comm="int8",
                              schedule="allgather", **kw)
        assert (i8["total"] < f64["total"]) == wins, (s, i8, f64)


def test_comm_bytes_model_structure():
    from repro.core.tuning import comm_bytes_model
    # world=1: a single device moves nothing
    one = comm_bytes_model(64, 64, 512, num_splits=9, world=1,
                           comm="int8")
    assert one["total"] == 0
    # fast-mode pair truncation drops whole anti-diagonal groups from
    # the partial-product traffic
    full = comm_bytes_model(64, 64, 512, num_splits=9, world=8,
                            comm="int8")
    diag = comm_bytes_model(64, 64, 512, num_splits=9, world=8,
                            comm="int8", pair_policy="diagonal")
    assert diag["partials"] < full["partials"]
    # batch scales the activation-side items; broadcast weights cross once
    b4 = comm_bytes_model(64, 64, 512, num_splits=9, world=8, comm="f64",
                          batch=4)
    b1 = comm_bytes_model(64, 64, 512, num_splits=9, world=8, comm="f64")
    assert b4["operands"] < 4 * b1["operands"]
    with pytest.raises(ValueError, match="layout"):
        comm_bytes_model(8, 8, 8, num_splits=9, world=2, layout="bogus")
    with pytest.raises(ValueError, match="comm"):
        comm_bytes_model(8, 8, 8, num_splits=9, world=2, comm="fp8")
    with pytest.raises(ValueError, match="schedule"):
        comm_bytes_model(8, 8, 8, num_splits=9, world=2, schedule="bogus")
    with pytest.raises(ValueError, match="world"):
        comm_bytes_model(8, 8, 8, num_splits=9, world=0)


def test_comm_bytes_model_scheme2():
    """Scheme II transport: k-shard int8 ships ell int32 residue planes
    (no f64 operand word ever crosses); m/n-shard gathers the packed
    ResidueWire at ell bytes/element vs f64's 8."""
    from repro.core.tuning import comm_bytes_model
    kw = dict(num_splits=9, world=8, scheme="ozaki2_fp64", num_moduli=15)
    f64 = comm_bytes_model(256, 256, 8192, layout="kshard", comm="f64",
                           **kw)
    i8 = comm_bytes_model(256, 256, 8192, layout="kshard", comm="int8",
                          **kw)
    assert i8["operands"] == 0 and i8["partials"] > 0
    assert f64["total"] > i8["total"]      # tall k amortizes the planes
    rs = comm_bytes_model(256, 256, 8192, layout="kshard", comm="int8",
                          schedule="reduce_scatter", **kw)
    assert rs["partials"] * 2 == i8["partials"]
    # mnshard honesty: ell=15 > 8 loses, ell=5 < 8 wins
    for ell, wins in ((5, True), (15, False)):
        g64 = comm_bytes_model(256, 256, 4096, num_splits=9, world=8,
                               layout="mnshard", comm="f64",
                               scheme="ozaki2_fp64", num_moduli=ell)
        gi8 = comm_bytes_model(256, 256, 4096, num_splits=9, world=8,
                               layout="mnshard", comm="int8",
                               scheme="ozaki2_fp64", num_moduli=ell)
        assert (gi8["total"] < g64["total"]) == wins, (ell, gi8, g64)
    with pytest.raises(ValueError, match="num_moduli"):
        comm_bytes_model(8, 8, 8, num_splits=9, world=2,
                         scheme="ozaki2_fp64")


# ----------------------------------------------------------------------------
# PipelinePlan.comm: validation, serialization, config threading
# ----------------------------------------------------------------------------

def test_plan_comm_validation_and_round_trip():
    plan = PipelinePlan(comm="int8", shard_axis="model")
    assert PipelinePlan.from_dict(plan.to_dict()) == plan
    with pytest.raises(ValueError, match="comm"):
        PipelinePlan(comm="fp8")
    # legacy serialized plans (pre-comm) load with the f64 default
    d = plan.to_dict()
    del d["comm"]
    assert PipelinePlan.from_dict(d).comm == "f64"


def test_plan_for_threads_comm():
    cfg = OzakiConfig(shard_axis="model", comm="int8")
    plan = plan_for(cfg)
    assert plan.comm == "int8" and plan.shard_axis == "model"
    back = apply_pipeline_plan(OzakiConfig(), plan)
    assert back.comm == "int8" and back.shard_axis == "model"
    sel = select_pipeline_plan(64, 64, 512, shard_axis="model", comm="int8")
    assert sel.comm == "int8"


@pytest.mark.parametrize("host_default", [True, False])
def test_interpret_default_follows_host(monkeypatch, host_default):
    """Every ``interpret=None`` default resolves to the one host default,
    ``kernels.ops.INTERPRET`` (on off a TPU, off on one), when the plan
    is built; an explicit value always wins."""
    from repro.core.modular import ModularConfig
    from repro.kernels import ops
    monkeypatch.setattr(ops, "INTERPRET", host_default)
    assert OzakiConfig().plan().interpret is host_default
    assert ModularConfig().plan(64).interpret is host_default
    assert select_pipeline_plan(64, 64, 64).interpret is host_default
    assert PipelinePlan().interpret is host_default
    assert OzakiConfig(interpret=True).plan().interpret is True
    assert PipelinePlan(interpret=False).interpret is False
