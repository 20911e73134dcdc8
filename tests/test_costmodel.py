"""Roofline cost-model tests: factorization enumeration/legality, the
analytic span-overlap reshard bytes, the scorer, and the pin that the
cluster generator's mesh plan IS the roofline top score."""

import pytest

from edl_tpu.parallel import costmodel


def _profile(**kw):
    kw.setdefault("n_layers", 8)
    kw.setdefault("d_model", 1024)
    kw.setdefault("n_heads", 16)
    kw.setdefault("seq_len", 512)
    return costmodel.transformer_profile(**kw)


def test_candidate_factorizations_cover_the_world():
    for f in costmodel.candidate_factorizations(8):
        assert f["dp"] * f["tp"] * f["pp"] * f["ep"] == 8
    caps = costmodel.candidate_factorizations(8, max_tp=2, max_pp=1,
                                              max_ep=1)
    assert all(f["tp"] <= 2 and f["pp"] == 1 and f["ep"] == 1
               for f in caps)
    assert {f["tp"] for f in caps} == {1, 2}


def test_legality_reasons():
    prof = _profile(n_heads=6, n_experts=0)
    ok = {"dp": 2, "tp": 2, "pp": 2, "ep": 1}
    assert costmodel.legality_reason(ok, prof, total_batch=16) is None
    assert "batch" in costmodel.legality_reason(
        dict(ok, dp=3), prof, total_batch=16)
    assert "heads" in costmodel.legality_reason(
        dict(ok, tp=4), prof, total_batch=16)
    assert "layers" in costmodel.legality_reason(
        dict(ok, pp=3), prof, total_batch=18)
    # no experts in the profile -> any ep>1 is illegal
    assert "experts" in costmodel.legality_reason(
        dict(ok, ep=2), prof, total_batch=16)


def test_device_spans_row_major():
    axes = {"dp": 2, "tp": 2}
    spans = costmodel.device_spans((8, 8), ("dp", "tp"), axes)
    # row-major: device = dp_coord * tp + tp_coord
    assert spans[0] == ((0, 4), (0, 4))
    assert spans[1] == ((0, 4), (4, 8))
    assert spans[2] == ((4, 8), (0, 4))
    assert spans[3] == ((4, 8), (4, 8))
    # absent / size-1 axes in a spec are ignored, not an error
    spans = costmodel.device_spans((8,), ("sp",), axes)
    assert all(s == ((0, 8),) for s in spans.values())


def test_tree_reshard_bytes_zero_wire_and_partial():
    src = costmodel.mesh_axes({"dp": 4})
    dst = costmodel.mesh_axes({"dp": 2, "tp": 2})
    # replicated and tp-sharded leaves slice locally on a dp -> dp x tp
    # transition (the source held everything / tp was size 1): zero wire
    moved, needed = costmodel.tree_reshard_bytes(
        [((16, 16), 4, (), ()),
         ((16, 16), 4, (None, "tp"), (None, "tp"))], src, dst)
    assert moved == 0
    assert needed > 0
    # a dp-sharded moment re-rows: each target device owns 8 rows but
    # held 4 under dp=4 -> 4 rows x 16 cols x 4 B x 4 devices move
    moved, needed = costmodel.tree_reshard_bytes(
        [((16, 16), 4, ("dp",), ("dp",))], src, dst)
    assert moved == 4 * 16 * 4 * 4
    assert needed == 8 * 16 * 4 * 4
    assert moved < needed


def test_step_time_penalizes_needless_model_parallelism():
    """With a batch big enough for pure dp, flat dp must outscore a tp
    mesh on a small dense model (the collectives only cost)."""
    prof = _profile()
    ranked = costmodel.score_factorizations(8, prof, total_batch=64)
    assert ranked, "no legal factorization"
    assert ranked[0]["dp"] == 8
    assert ranked[0]["score"] <= ranked[-1]["score"]


def test_small_batch_forces_model_parallelism():
    """total_batch=4 on world 8: dp>4 is illegal, so the top choice
    must spend the rest of the world on model axes."""
    prof = _profile()
    best = costmodel.best_factorization(8, prof, total_batch=4)
    assert best is not None
    assert best["dp"] <= 4
    assert best["tp"] * best["pp"] * best["ep"] == 8 // best["dp"]


def test_score_includes_reshard_cost_from_current():
    """Moving away from the current mesh costs wire seconds: with a
    tiny amortization window, keeping the current factorization must
    beat an equal-step-time move."""
    prof = _profile()
    cur = {"dp": 4, "tp": 2, "pp": 1, "ep": 1}
    ranked = costmodel.score_factorizations(
        8, prof, total_batch=64, current=cur, amortize_steps=1e-6)
    stay = next(r for r in ranked
                if all(r[k] == cur[k] for k in cur))
    assert stay["reshard_bytes"] == 0
    assert ranked[0] is stay


def test_planner_remembers_its_previous_choice():
    prof = _profile()
    plan = costmodel.make_planner(prof, total_batch=64)
    first = plan(8)
    assert first == {k: costmodel.best_factorization(
        8, prof, 64)[k] for k in ("dp", "tp", "pp", "ep")}
    # the second call scores the move FROM the first choice
    second = plan(4)
    want = costmodel.best_factorization(4, prof, 64, current=first)
    assert second == {k: want[k] for k in ("dp", "tp", "pp", "ep")}


def test_generator_mesh_plan_matches_roofline_top_score():
    """The acceptance pin: for two world sizes, the cluster generator's
    committed mesh (Generator._plan_mesh with a costmodel planner) IS
    the roofline top score for that world, reshard cost included."""
    from edl_tpu.controller import cluster as cluster_mod
    from edl_tpu.controller.cluster_generator import Generator

    prof = _profile()
    gen = Generator.__new__(Generator)
    gen._mesh_planner = costmodel.make_planner(prof, total_batch=16)

    def cluster_of(world):
        c = cluster_mod.Cluster()
        pod = type("PodStub", (), {})()
        pod.trainers = []
        pod.devices = list(range(world))
        c.pods = [pod]
        return c

    current = None
    cur_factors = None
    for world in (8, 4):
        new = cluster_of(world)
        gen._plan_mesh(new, current)
        want = costmodel.best_factorization(world, prof, 16,
                                            current=cur_factors)
        assert new.mesh == {k: want[k] for k in ("dp", "tp", "pp", "ep")}
        current, cur_factors = new, new.mesh


def test_generator_mesh_plan_fails_open():
    from edl_tpu.controller import cluster as cluster_mod
    from edl_tpu.controller.cluster_generator import Generator

    gen = Generator.__new__(Generator)
    gen._mesh_planner = lambda world, current=None: 1 / 0
    new = cluster_mod.Cluster()
    pod = type("PodStub", (), {})()
    pod.trainers = []
    pod.devices = [0, 1]
    new.pods = [pod]
    gen._plan_mesh(new, None)  # must not raise
    assert new.mesh is None


def test_reshard_cost_is_zero_when_staying_put():
    prof = _profile()
    f = {"dp": 4, "tp": 2, "pp": 1, "ep": 1}
    assert costmodel.reshard_cost_bytes(prof, f, f) == 0
    assert costmodel.reshard_cost_bytes(prof, None, f) == 0
    moved = costmodel.reshard_cost_bytes(
        prof, {"dp": 8, "tp": 1, "pp": 1, "ep": 1}, f)
    assert moved > 0


def test_step_time_breakdown_fields():
    prof = _profile(n_experts=8)
    t = costmodel.step_time_s({"dp": 2, "tp": 2, "pp": 2, "ep": 1},
                              prof, total_batch=16)
    for k in ("total_s", "compute_s", "hbm_s", "bubble", "dp_s",
              "tp_s", "pp_s", "ep_s"):
        assert k in t
    assert t["total_s"] > 0
    assert t["bubble"] == pytest.approx(
        1.0 + 1.0 / costmodel.PIPELINE_MICROBATCHES)


def test_peaks_table_is_keyed_by_device_kind_and_unknown_kinds_raise():
    """One table of published peaks, keyed by what JAX calls the chip;
    a device that is not in it is an error, never a default."""
    v5e = costmodel.chip_peaks("TPU v5 lite")
    assert v5e is costmodel.CHIP_V5E
    # Google Cloud "TPU v5e": TFLOP/s bf16, GB/s and GB of HBM
    assert (v5e["bf16_tflops"], v5e["hbm_gbps"], v5e["hbm_gb"]) == (
        197, 819, 16)
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            costmodel.chip_peaks(kind)
