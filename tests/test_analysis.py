"""Plan static analysis (``repro.analysis``): the op-graph walkers, the
overlap-materialization verdicts, the LAG0xx deployment linter, and the
refusal gates wired into ``tune()``, ``PlanRepository.put``,
``PlanBinding`` and the CLIs."""
import copy
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import (ChunkLoop, CollectiveOp, Finding, OpGraph,
                            PlanLintError, check_plan, collective_bytes,
                            errors, format_findings, graph_from_hlo,
                            graph_from_jaxpr, lint_plan, rules)
from repro.analysis.__main__ import main as analysis_main
from repro.configs import get_config, get_smoke_config
from repro.core import (ParallelPlan, TunedPlan, extract_decode_workload,
                        extract_workload, session, tune)
from repro.core.comm_params import CommConfig
from repro.core.plan_repo import PlanRepository
from repro.launch.mesh import make_mesh
from repro.parallel import collectives as C

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(autouse=True)
def _clean_plan_state():
    yield
    C.install_runtime_plan({})


def _fsdp_wl(layers=2):
    return extract_workload(get_config("llama3-8b"),
                            ParallelPlan(kind="fsdp", dp=8),
                            seq=2048, global_batch=16, layers=layers)


@pytest.fixture(scope="module")
def wl():
    return _fsdp_wl()


@pytest.fixture(scope="module")
def plan(wl):
    return tune(wl, "tpu-v5e", method="nccl")


def _mutant(plan):
    """A deep, independently mutable copy of a tuned plan."""
    return copy.deepcopy(plan)


# ---------------------------------------------------------------------------
# ir: jaxpr walker
# ---------------------------------------------------------------------------

def test_jaxpr_walker_finds_collective_chunk_loop():
    mesh = make_mesh((jax.device_count(),), ("dp",))
    grads = {"w": jnp.ones((8, 4))}
    fn = jax.shard_map(
        lambda t: C.psum_tree_chunked(t, "dp", num_chunks=4),
        mesh=mesh, in_specs=({"w": P("dp")},), out_specs={"w": P("dp")})
    g = graph_from_jaxpr(jax.make_jaxpr(fn)(grads))
    loops = g.chunk_loops("allreduce", trip=4)
    assert loops and loops[0].n_collectives == 1
    assert g.count("allreduce") >= 1
    # the in-loop collective carries the loop's trip count
    assert any(c.kind == "allreduce" and c.trip == 4 for c in g.collectives)


def test_jaxpr_walker_compute_only_loop():
    def f(x):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    g = graph_from_jaxpr(jax.make_jaxpr(f)(jnp.ones((4, 4))))
    loops = g.chunk_loops(None, trip=3)
    assert loops and loops[0].has_compute and not loops[0].kinds
    assert not g.collectives


# ---------------------------------------------------------------------------
# ir: HLO text walker (format-stable fixture)
# ---------------------------------------------------------------------------

# trimmed but syntactically faithful post-SPMD dump: a counted while whose
# body holds a reduce-scatter + dot (tuple-typed params — the regression
# that hid loop bodies from the block parser), plus an async all-gather
# pair and a collective-permute at top level
_HLO_FIXTURE = """\
HloModule toy, entry_computation_layout={(f32[8,16])->f32[8,16]}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

%wide.body (param.1: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8,16]) %p), index=0
  %x = f32[8,16]{1,0} get-tuple-element((s32[], f32[8,16]) %p), index=1
  %rs = f32[2,16]{1,0} reduce-scatter(f32[8,16]{1,0} %x), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  %d = f32[2,16]{1,0} dot(f32[2,16]{1,0} %rs, f32[16,16]{1,0} %rs), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,16]) tuple(s32[] %i, f32[8,16] %x)
}

%wide.cond (param.2: (s32[], f32[8,16])) -> pred[] {
  %p2 = (s32[], f32[8,16]) parameter(0)
  %i2 = s32[] get-tuple-element((s32[], f32[8,16]) %p2), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i2, s32[] %n), direction=LT
}

ENTRY %main (param.0: f32[8,16]) -> f32[8,16] {
  %x0 = f32[8,16]{1,0} parameter(0)
  %ags = (f32[4,16], f32[8,16]) all-gather-start(f32[4,16]{1,0} %x0), channel_id=2, replica_groups={{0,1}}, dimensions={0}
  %agd = f32[8,16]{1,0} all-gather-done((f32[4,16], f32[8,16]) %ags)
  %cp = f32[8,16]{1,0} collective-permute(f32[8,16]{1,0} %agd), channel_id=3, source_target_pairs={{0,1},{1,0}}
  %w = (s32[], f32[8,16]) while((s32[], f32[8,16]) %cp), condition=%wide.cond, body=%wide.body
  ROOT %out = f32[8,16]{1,0} get-tuple-element((s32[], f32[8,16]) %w), index=1
}
"""


def test_hlo_walker_counted_while_with_tuple_params():
    g = graph_from_hlo(_HLO_FIXTURE)
    loops = g.chunk_loops("reducescatter", trip=4)
    assert loops and loops[0].has_compute and loops[0].source == "while"
    # async pair counted once; -done skipped
    assert g.count("allgather") == 1
    assert g.count("permute") == 1
    assert g.count("reducescatter") == 1
    rs = next(c for c in g.collectives if c.kind == "reducescatter")
    assert rs.trip == 4   # loop-body collective inherits the while's trip


def test_collective_bytes_counts_async_pairs_once():
    out = collective_bytes(_HLO_FIXTURE)
    assert out["count"] == 3
    assert out["all-gather"] == 4 * 16 * 4.0     # -start result, once
    assert out["reduce-scatter"] == 2 * 16 * 4.0
    assert out["collective-permute"] == 8 * 16 * 4.0
    assert out["all-reduce"] == 0.0 and out["all-to-all"] == 0.0


def test_dryrun_parser_delegates_to_shared_op_table():
    from repro.launch.dryrun import parse_collective_bytes

    assert parse_collective_bytes(_HLO_FIXTURE) == collective_bytes(
        _HLO_FIXTURE)


# ---------------------------------------------------------------------------
# overlap: verdict semantics on synthetic graphs
# ---------------------------------------------------------------------------

def _row(site, cls, strategy, nc, tier="exact"):
    return C.SiteResolution(site=site, cls=cls, strategy=strategy,
                            num_chunks=nc, matched_key=site, tier=tier)


def _verify(plan, graph, rows):
    from repro.analysis.overlap import verify

    return verify(plan, graph, rows)


def test_verdict_materialized_degraded_absent():
    plan = {"tp.l0.rs": C.CollectiveRuntime("chunked", 4)}
    rows = [_row("tp.l0.rs", "rs", "chunked", 4)]
    loop = ChunkLoop(trip=4, kinds=("reducescatter",), n_collectives=1,
                     has_compute=True, depth=0)
    coll = CollectiveOp(kind="reducescatter", raw="reduce-scatter")

    good = OpGraph(source="hlo", collectives=[coll], loops=[loop])
    assert _verify(plan, good, rows).verdict_for("tp.l0.rs") == "MATERIALIZED"

    # collective present but monolithic (no trip-4 loop) -> DEGRADED
    flat = OpGraph(source="hlo", collectives=[coll])
    r = _verify(plan, flat, rows)
    assert r.verdict_for("tp.l0.rs") == "DEGRADED" and not r.ok()
    assert r.ok(allow_degraded=True)

    # class collective missing entirely -> ABSENT
    empty = OpGraph(source="hlo")
    r = _verify(plan, empty, rows)
    assert r.verdict_for("tp.l0.rs") == "ABSENT"
    assert not r.ok(allow_degraded=True)


def test_verdict_absent_when_trace_missed_the_plan():
    plan = {"tp.l0.rs": C.CollectiveRuntime("chunked", 4)}
    # trace recorded XLA defaults: the plan was not installed
    rows = [_row("tp.l0.rs", "rs", "xla", 1, tier="default")]
    loop = ChunkLoop(trip=4, kinds=("reducescatter",), n_collectives=1,
                     has_compute=True, depth=0)
    g = OpGraph(source="jaxpr", loops=[loop],
                collectives=[CollectiveOp(kind="reducescatter", raw="rs")])
    v = _verify(plan, g, rows).verdicts[0]
    assert v.verdict == "ABSENT" and "not installed" in v.detail


def test_verdict_nc1_trivially_materialized_and_untuned_excluded():
    plan = {"tp.l0.rs": C.CollectiveRuntime("chunked", 1)}
    rows = [_row("tp.l0.rs", "rs", "chunked", 1),
            _row("other.ar", "ar", "xla", 1, tier="default")]
    r = _verify(plan, OpGraph(source="jaxpr"), rows)
    assert r.verdict_for("tp.l0.rs") == "MATERIALIZED"
    assert r.untuned == ["other.ar"] and r.ok()


def test_two_sites_same_signature_need_two_loops():
    plan = {"a.rs": C.CollectiveRuntime("chunked", 2),
            "b.rs": C.CollectiveRuntime("chunked", 2)}
    rows = [_row("a.rs", "rs", "chunked", 2), _row("b.rs", "rs", "chunked", 2)]
    loop = ChunkLoop(trip=2, kinds=("reducescatter",), n_collectives=1,
                     has_compute=True, depth=0)
    coll = CollectiveOp(kind="reducescatter", raw="rs")
    one = OpGraph(source="hlo", collectives=[coll], loops=[loop])
    r = _verify(plan, one, rows)
    # multiset supply: a single loop cannot vouch for both tuned sites
    assert sorted(v.verdict for v in r.verdicts) == ["DEGRADED",
                                                     "MATERIALIZED"]
    two = OpGraph(source="hlo", collectives=[coll, coll], loops=[loop, loop])
    assert all(v.verdict == "MATERIALIZED"
               for v in _verify(plan, two, rows).verdicts)


def test_unobserved_plan_sites_are_not_false_positives(plan):
    r = _verify(plan, OpGraph(source="jaxpr"), [])
    assert not r.verdicts and r.ok()
    assert set(r.unobserved) == {s.get("site") or s["name"]
                                 for s in plan.sites}


# ---------------------------------------------------------------------------
# overlap: trace_and_verify on a real traced program
# ---------------------------------------------------------------------------

def test_trace_and_verify_roundtrip_and_no_install_control():
    from repro.analysis.overlap import trace_and_verify

    mesh = make_mesh((jax.device_count(),), ("dp",))
    plan = {"acc.step0.rs_grads": C.CollectiveRuntime("chunked", 4)}
    grads = {"w": jnp.ones((8, 4))}

    def fn(t):
        return jax.shard_map(
            lambda g: C.psum_tree_chunked(g, "dp", site="acc.step0.rs_grads"),
            mesh=mesh, in_specs=({"w": P("dp")},),
            out_specs={"w": P("dp")})(t)

    rep = trace_and_verify(plan, fn, grads)
    assert rep.verdict_for("acc.step0.rs_grads") == "MATERIALIZED"
    # deliberately-uninstalled control: the same trace flips to ABSENT
    off = trace_and_verify(plan, fn, grads, install=False)
    assert off.verdict_for("acc.step0.rs_grads") == "ABSENT"


def test_record_site_resolutions_tiers_and_nesting():
    plan = {"a.b": C.CollectiveRuntime("chunked", 2)}
    with C.use_runtime_plan(plan):
        with C.record_site_resolutions() as outer:
            C.runtime_for("a.b.c", "rs")
            with C.record_site_resolutions() as inner:
                C.runtime_for("zz", "rs")
            C.runtime_for("a.b", None)
    assert [(r.site, r.tier) for r in outer] == [("a.b.c", "prefix"),
                                                 ("a.b", "exact")]
    assert [(r.site, r.tier, r.matched_key) for r in inner] == [
        ("zz", "default", "")]


# ---------------------------------------------------------------------------
# lint: healthy plans are quiet; each rule catches its seeded defect
# ---------------------------------------------------------------------------

def test_rule_catalog_is_stable():
    cat = rules()
    assert set(cat) == {"LAG001", "LAG002", "LAG003", "LAG004", "LAG010",
                        "LAG020", "LAG021", "LAG030", "LAG031", "LAG040"}
    assert {c for c, r in cat.items() if r.severity == "error"} == {
        "LAG001", "LAG003", "LAG004", "LAG020", "LAG030", "LAG040"}
    assert all(r.doc for r in cat.values())


def test_healthy_plan_lints_clean(plan, wl):
    assert lint_plan(plan) == []
    assert lint_plan(plan, workload=wl) == []
    assert check_plan(plan, workload=wl) == []


def _codes(findings):
    return {f.code for f in findings}


def test_lag001_dead_entry(plan):
    m = _mutant(plan)
    m.configs[(999, 0)] = CommConfig()
    f = lint_plan(m)
    assert _codes(f) == {"LAG001"} and errors(f)
    assert "(group=999, comm=0)" in f[0].message


def test_lag002_untuned_site(plan):
    m = _mutant(plan)
    key = next(iter(m.configs))
    del m.configs[key]
    f = lint_plan(m)
    assert "LAG002" in _codes(f) and not errors(f)
    assert all(x.severity == "warning" for x in f)


def test_lag003_lag004_duplicate_shadowed_site(plan):
    m = _mutant(plan)
    first = m.sites[0]
    dup = dict(first, group="dup-group")
    # conflicting knobs for the same SiteId: huge chunk_kb lowers to nc=1
    m.configs[("dup-group", dup["comm"])] = CommConfig(
        algorithm="ring", chunk_kb=1 << 20)
    m.sites.append(dup)
    f = lint_plan(m)
    assert {"LAG003", "LAG004"} <= _codes(f)
    sid = first.get("site") or first["name"]
    assert any(x.code == "LAG004" and x.site == sid for x in f)


def test_lag010_indivisible_chunk(plan):
    m = _mutant(plan)
    row = next(s for s in m.sites if s["kind"] != "reducescatter")
    row["bytes"] = 1000003.0   # prime-ish payload: no nc>1 divides it
    m.configs[(row["group"], row["comm"])] = CommConfig(
        algorithm="ring", chunk_kb=256)   # lowers to nc=4
    f = lint_plan(m, select=["LAG010"])
    assert f and f[0].site == (row.get("site") or row["name"])
    assert "cannot evenly divide" in f[0].message


def test_lag020_inter_site_in_flat_plan(plan):
    m = _mutant(plan)
    m.sites[0]["tier"] = "inter"
    f = lint_plan(m, select=["LAG020"])
    assert f and f[0].severity == "error"
    assert "topology" in f[0].message


def test_lag021_hierarchical_plan_with_no_inter_site(plan):
    m = _mutant(plan)
    m.topology = {"fingerprint": "f" * 12, "name": "two_pod",
                  "spec": {"pods": 2}}
    f = lint_plan(m, select=["LAG021"])
    assert f and f[0].severity == "warning" and "2 pods" in f[0].message


def test_lag030_provenance_drift(plan, wl):
    from repro.core import two_pod

    # (a) hand-edited topology fingerprint
    topo = two_pod("tpu-v5e", "dcn")
    hwl = extract_workload(get_config("llama3-8b"),
                           ParallelPlan(kind="fsdp", dp=8, pods=2,
                                        accum_steps=2),
                           seq=2048, global_batch=16, layers=2)
    hplan = tune(hwl, topology=topo, method="nccl")
    assert lint_plan(hplan, select=["LAG030"]) == []
    hm = _mutant(hplan)
    hm.topology["fingerprint"] = "deadbeef"
    f = lint_plan(hm, select=["LAG030"])
    assert f and "hand-edited" in f[0].message

    # (b) plan applied against a structurally different workload
    other = _fsdp_wl(layers=4)
    f = lint_plan(plan, workload=other, select=["LAG030"])
    assert f and "fingerprint" in f[0].message


def test_lag031_band_unservable(plan):
    m = _mutant(plan)
    m.structure = ""
    f = lint_plan(m, select=["LAG031"])
    assert f and "tolerance-band" in f[0].message
    m2 = _mutant(plan)
    m2.shape = {"seq": 0, "global_batch": 16}
    f2 = lint_plan(m2, select=["LAG031"])
    assert f2 and "seq" in f2[0].message


def test_lag040_malformed_lineage(plan):
    good = _mutant(plan)
    good.lineage = {"retuned_from": "abc", "chain": ["abc"], "generation": 1}
    assert lint_plan(good, select=["LAG040"]) == []
    for lineage in ({"retuned_from": "b", "chain": ["a"]},
                    {"retuned_from": "b", "chain": []},
                    {"retuned_from": None, "chain": ["a"]},
                    {"chain": "not-a-list"}):
        m = _mutant(plan)
        m.lineage = lineage
        assert _codes(lint_plan(m, select=["LAG040"])) == {"LAG040"}, lineage


def test_findings_sorted_and_formatted(plan):
    m = _mutant(plan)
    m.configs[(999, 0)] = CommConfig()       # LAG001 error
    del m.configs[next(k for k in m.configs if k != (999, 0))]
    f = lint_plan(m)                                 # + LAG002 warnings
    assert f[0].severity == "error"                  # most severe first
    text = format_findings(f, label="demo.json")
    assert text.startswith(f"analysis: {len(f)} finding(s) (1 error(s), ")
    assert "in demo.json" in text and "LAG001 error:" in text


# ---------------------------------------------------------------------------
# refusal gates: check_plan, tune(lint=), put(lint=), PlanBinding
# ---------------------------------------------------------------------------

def _broken(plan):
    m = _mutant(plan)
    m.configs[(999, 0)] = CommConfig()   # one LAG001 ERROR
    return m


def test_check_plan_raises_with_findings_attached(plan):
    b = _broken(plan)
    with pytest.raises(PlanLintError, match="LAG001.*lint='off'") as ei:
        check_plan(b, label="unit plan")
    assert ei.value.findings and "unit plan" in str(ei.value)


def test_tune_lint_gate(wl):
    p = tune(wl, "tpu-v5e", method="nccl", lint="error")
    assert isinstance(p, TunedPlan)
    with pytest.raises(ValueError, match="lint="):
        tune(wl, "tpu-v5e", method="nccl", lint="bogus")


def test_repo_put_lint_gate(tmp_path, plan):
    repo = PlanRepository(tmp_path)
    b = _broken(plan)
    with pytest.raises(PlanLintError, match="LAG001"):
        repo.put(b, lint="error")
    repo.put(plan, lint="error")    # healthy plan passes the gate
    with pytest.raises(ValueError, match="lint="):
        repo.put(plan, lint="bogus")


def _decode_plan():
    cfg = get_smoke_config("llama3-8b")
    wl = extract_decode_workload(cfg, ParallelPlan(kind="tp", tp=2),
                                 global_batch=4, seq=64)
    return cfg, tune(wl, "tpu-v5e", method="nccl")


def test_plan_binding_refuses_error_plans_with_override():
    from repro.serving.plans import PlanBinding

    cfg, dplan = _decode_plan()
    broken = _broken(dplan)
    with pytest.raises(PlanLintError, match="LAG001"):
        PlanBinding(cfg, plan=broken)
    # override flag: same plan binds, findings kept for inspection
    b = PlanBinding(cfg, plan=broken, lint="off")
    assert b.bound and b.lint_findings == []
    w = PlanBinding(cfg, plan=dplan, lint="warn")
    assert w.lint_findings == []
    with pytest.raises(ValueError, match="lint="):
        PlanBinding(cfg, plan=dplan, lint="loud")


def test_engines_plumb_plan_lint():
    from repro.models import model as M
    from repro.serving import make_engine

    cfg, dplan = _decode_plan()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    broken = _broken(dplan)
    for mode, kw in (("fixed", dict(batch_size=2)), ("continuous",
                                                     dict(slots=2))):
        with pytest.raises(PlanLintError, match="LAG001"):
            make_engine(cfg, params, mode=mode, max_seq=32, plan=broken, **kw)
        eng = make_engine(cfg, params, mode=mode, max_seq=32, plan=broken,
                          plan_lint="off", **kw)
        assert eng is not None


# ---------------------------------------------------------------------------
# runtime LAG010 warning (satellite: structured + deduped)
# ---------------------------------------------------------------------------

def test_degraded_warning_structured_and_deduped():
    mesh = make_mesh((jax.device_count(),), ("dp",))
    grads = {"w": jnp.ones((5, 2))}   # 5 % 2 != 0
    fn = jax.shard_map(
        lambda t: C.psum_tree_chunked(t, "dp", num_chunks=2,
                                      site="acc.step0.rs_grads"),
        mesh=mesh, in_specs=({"w": P("dp")},), out_specs={"w": P("dp")})
    with pytest.warns(C.CollectiveDegradedWarning) as rec:
        jax.make_jaxpr(fn)(grads)
    ws = [w.message for w in rec
          if isinstance(w.message, C.CollectiveDegradedWarning)]
    assert len(ws) == 1
    assert ws[0].code == "LAG010" and ws[0].site == "acc.step0.rs_grads"
    assert "[LAG010]" in str(ws[0]) and "acc.step0.rs_grads" in str(ws[0])
    # deduped per site per process: a retrace stays silent...
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", C.CollectiveDegradedWarning)
        jax.make_jaxpr(lambda t: fn(t))(grads)
    # ...until the dedupe state is reset
    C.reset_degraded_warnings()
    with pytest.warns(C.CollectiveDegradedWarning):
        jax.make_jaxpr(lambda t: fn(t))(grads)


# ---------------------------------------------------------------------------
# CLIs: repro.analysis lint exit codes; session diff on malformed input
# ---------------------------------------------------------------------------

def test_cli_lint_exit_codes(tmp_path, plan, capsys):
    good = tmp_path / "good.json"
    plan.save(str(good))
    assert analysis_main(["lint", str(good)]) == 0
    out = capsys.readouterr().out
    assert "analysis: 0 finding(s)" in out and str(good) in out

    broken = tmp_path / "broken.json"
    _broken(plan).save(str(broken))
    assert analysis_main(["lint", str(broken)]) == 1
    # seeded-fixture contract: exact expected codes invert the exit
    assert analysis_main(["lint", str(broken), "--expect", "LAG001"]) == 0
    assert analysis_main(["lint", str(broken), "--expect",
                          "LAG001,LAG002"]) == 1
    capsys.readouterr()

    mangled = tmp_path / "mangled.json"
    mangled.write_text("{this is not a plan")
    assert analysis_main(["lint", str(mangled)]) == 2
    assert "not a readable TunedPlan artifact" in capsys.readouterr().err
    notaplan = tmp_path / "notaplan.json"
    notaplan.write_text(json.dumps({"version": 999}))
    assert analysis_main(["lint", str(notaplan)]) == 2


def test_session_diff_cli_malformed_input_exits_2(tmp_path, plan, capsys):
    good = tmp_path / "a.json"
    plan.save(str(good))
    assert session._main(["diff", str(good), str(good)]) == 0
    capsys.readouterr()
    for text in ("{oops", json.dumps([1, 2, 3]), json.dumps({"v": 1})):
        bad = tmp_path / "bad.json"
        bad.write_text(text)
        assert session._main(["diff", str(good), str(bad)]) == 2
        err = capsys.readouterr().err
        assert "not a readable TunedPlan artifact" in err
    assert session._main(["diff", str(good),
                          str(tmp_path / "missing.json")]) == 2


# ---------------------------------------------------------------------------
# verify-overlap end to end on an 8-device mesh (subprocess)
# ---------------------------------------------------------------------------

_VERIFY_SCRIPT = r"""
import sys
from repro.configs import get_config
from repro.core import ParallelPlan, extract_workload, tune
from repro.analysis.exercise import exercise_plan

wl = extract_workload(get_config("llama3-8b"),
                      ParallelPlan(kind="fsdp", dp=8, accum_steps=2),
                      seq=2048, global_batch=64, layers=2)
plan = tune(wl, "tpu-v5e")
plan.save(sys.argv[1])

report = exercise_plan(plan)
print(report.format())
assert report.verdicts and report.ok(), report.format()
chunked = [v for v in report.verdicts if v.num_chunks > 1]
assert chunked, "tuned plan must chunk at least one site"
off = exercise_plan(plan, install=False)
assert all(v.verdict == "ABSENT" for v in off.verdicts), off.format()
print("SUBPROCESS_OK")
"""


@pytest.mark.slow
def test_verify_overlap_exercises_tuned_plan(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    saved = tmp_path / "plan.json"
    out = subprocess.run([sys.executable, "-c", _VERIFY_SCRIPT, str(saved)],
                         env=env, capture_output=True, text=True, timeout=560)
    assert "SUBPROCESS_OK" in out.stdout, out.stdout + out.stderr

    # the CLI front door agrees: lint clean + verify-overlap exit 0
    cli = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "lint", str(saved)],
        env=env, capture_output=True, text=True, timeout=560)
    assert cli.returncode == 0, cli.stdout + cli.stderr
    cli = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "verify-overlap", str(saved)],
        env=env, capture_output=True, text=True, timeout=560)
    assert cli.returncode == 0 and "MATERIALIZED" in cli.stdout, (
        cli.stdout + cli.stderr)
