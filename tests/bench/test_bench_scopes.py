"""The program's named scopes and ``bench/scopes.py``, which reads a traced
run's device time by them: the scopes reach the compiled step (every
collective site resolved while tracing names a ``site:`` scope on
collectives of both directions, each component scope is there, on the
sited trunk and on the scan trunk); the step ``scopes`` compiles again is
the step a run compiles; the reading on synthetic events and on one
recorded step of the 4-chip cell."""
import gzip
import json
import os
import re
import subprocess
import sys

import pytest

from bench import scopes as S
from bench import trace as TR
from bench.run import BENCH, ROOT, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
YI = "yi-34b-2l.train.tp4-plan"
COMPONENTS = ("embed", "attention", "mlp", "loss", "optimizer")
# one step of the yi cell on a 4-chip TPU v5e host, chips 0 and 1, with the
# op_name and the collectives of its operations from the compiled step
YI_EXCERPT = os.path.join(HERE, "data", "v5e_yi_trace_excerpt.json.gz")

# run with four CPU devices: the yi cell's mesh spans them.  For each plan,
# the step a run of the traffic loop compiles and the one ``scopes``
# compiles again, with the sites it resolved.
SCRIPT = """
import copy, json, re, sys, tempfile
sys.path[:0] = [{root!r}, {root!r} + "/src", {here!r}]
import tiny
from bench import scopes
from bench.traffic import train
out = {{}}
for plan in ("tp:4", "none"):
    s = tiny.spec({cell!r})
    s["cellfile"]["plan"] = plan
    with tempfile.TemporaryDirectory() as d:
        rec = train.run(s["config"], s["traffic"], s["cellfile"], seed=7,
                        seconds=0.05, trace_dir=d, log=lambda m: None)
    hlo, sites = scopes.compiled_step(s["config"], s["traffic"],
                                      s["cellfile"])
    out[plan] = {{"hlo": hlo, "run_hlo": rec["hlo"], "sites": sorted(sites)}}
print(json.dumps(out))
"""


def scopes(hlo: str):
    """``(site, direction)`` of every collective instruction, and every
    component on some instruction's path."""
    names = S.op_names(hlo)
    collectives = S.collective_names(hlo)
    sites, comps = set(), set()
    for name, op_name in names.items():
        site, comp, bwd = S.scope_of(op_name)
        comps.add(comp)
        if site and name in collectives:
            sites.add((site, "bwd" if bwd else "fwd"))
    return sites, comps


def instruction_lines(hlo: str):
    """The instructions of a compiled module's text, as text, without the
    call-site references that differ with where the step was built."""
    return [re.sub(r",? stack_frame_id=\d+", "", line)
            for line in hlo.splitlines() if S._INSTRUCTION.match(line)]


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=ROOT, here=HERE, cell=YI)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("way", ["fwd", "bwd"])
def test_every_resolved_site_names_its_collectives(compiled, way):
    o = compiled["tp:4"]
    assert o["sites"] == ["tp.layer0.mlp.ag", "tp.layer0.mlp.rs",
                          "tp.layer1.mlp.ag", "tp.layer1.mlp.rs"]
    sites, _ = scopes(o["hlo"])
    assert {s for s, w in sites if w == way} == set(o["sites"])


@pytest.mark.parametrize("plan,comp", [("tp:4", c) for c in COMPONENTS] + [
    ("tp:4", "layer_params")] + [("none", c) for c in COMPONENTS])
def test_component_scope_compiled(compiled, plan, comp):
    o = compiled[plan]
    sites, comps = scopes(o["hlo"])
    assert comp in comps
    if plan == "none":          # the scan trunk has no site
        assert not sites and not o["sites"] and "layer_params" not in comps


@pytest.mark.parametrize("plan", ["tp:4", "none"])
def test_compiled_again_is_the_step_the_run_compiled(compiled, plan):
    o = compiled[plan]
    assert o["hlo"].splitlines()[0] == o["run_hlo"].splitlines()[0]
    assert instruction_lines(o["hlo"]) == instruction_lines(o["run_hlo"])


def test_latest_trace(tmp_path):
    assert S.latest_trace(str(tmp_path)) is None
    for cell, t in (("a", 1), ("b", 2)):
        d = tmp_path / "bench_out" / cell / "trace" / "plugins" / "p"
        d.mkdir(parents=True)
        (d / "x.xplane.pb").write_bytes(b"")
        os.utime(d / "x.xplane.pb", (t, t))
    assert S.latest_trace(str(tmp_path)) == str(
        tmp_path / "bench_out" / "b" / "trace")


# the same window read by scope: one device, one step, window 0..50
AG = "jit(f)/jvp(mlp)/site:tp.layer0.mlp.ag/shard_map"
RS = "jit(f)/transpose(jvp(mlp))/site:tp.layer0.mlp.rs/shard_map"
SCOPED = {
    "devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 0, 10], ["fusion.2", 10, 20],
                ["collective-permute-start.3", 20, 22], ["fusion.4", 22, 30],
                ["collective-permute-done.3", 30, 31], ["all-gather.5", 31, 35],
                ["all-reduce.6", 35, 38], ["fusion.7", 38, 40],
                ["fusion.8", 40, 45], ["fusion.10", 44, 46],
                ["reduce_scatter.11", 46, 48], ["while.9", 0, 48]],
        # the permute's flight runs under fusion.4
        "async": [["collective-permute-start.3", 20, 30]]}},
    "host": [["bench.data", 0, 1], ["bench.dispatch", 1, 2],
             ["bench.sync", 2, 50]],
}
NAMES = {
    "fusion.1": "jit(f)/jvp(attention)/dot_general",
    "fusion.2": AG + "/dot_general",
    "collective-permute-start.3": AG + "/ppermute",
    "collective-permute-done.3": AG + "/ppermute",
    "fusion.4": RS + "/dot_general",
    "all-gather.5": RS + "/all_gather",
    "all-reduce.6": "jit(f)/transpose(jvp(attention))/dot_general",
    # fusion.7 has no op_name
    "fusion.8": "jit(f)/optimizer/mul",
    "fusion.10": "jit(f)/loss/add",
    # a synchronous collective the lowering named after its primitive: a
    # collective by its opcode alone
    "reduce_scatter.11": "jit(f)/jvp(mlp)/site:tp.layer0.mlp.rs/"
                         "shard_map/reduce_scatter",
    "while.9": "jit(f)/jvp(mlp)/while",
}
COLLECTIVES = {"reduce_scatter.11"}


@pytest.mark.parametrize("text", [
    '%fusion.12 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
    'calls=%fc, metadata={op_name="jit(f)/jvp(mlp)/mul" source_line=3}',
    'fusion.12 = f32[4]{0} fusion(f32[4]{0} p), kind=kLoop, calls=fc, '
    'metadata={op_name="jit(f)/jvp(mlp)/mul" source_line=3}',
    '  ROOT %fusion.12 = (f32[4]{0}, s32[]) fusion(%p), kind=kLoop, '
    'metadata={op_name="jit(f)/jvp(mlp)/mul"}'])
def test_op_names(text):
    hlo = "\n".join(["HloModule m, entry_computation_layout={()->f32[4]}",
                     "ENTRY %main () -> f32[4] {", "  %p = f32[4]{0} "
                     "parameter(0)", text, "}"])
    assert S.op_names(hlo) == {"fusion.12": "jit(f)/jvp(mlp)/mul"}
    assert S.instructions(hlo) == {"p", "fusion.12"}
    assert S.collective_names(hlo) == set()


@pytest.mark.parametrize("text,name", [
    ('%reduce_scatter.15 = f32[2,1024,7168]{2,1,0:T(8,128)} reduce-scatter('
     'f32[2,4096,7168]{2,1,0:T(8,128)} %dot), replica_groups={{0,1,2,3}}, '
     'dimensions={1}, to_apply=%add', "reduce_scatter.15"),
    ('%collective-permute-start.11 = (f32[2]{0}, f32[2]{0}) '
     'collective-permute-start(f32[2]{0} %x), source_target_pairs={{0,1}}',
     "collective-permute-start.11"),
    ('ppermute.3 = f32[2]{0} collective-permute(f32[2]{0} x), '
     'source_target_pairs={{0,1}}', "ppermute.3"),
    ('%fusion.2 = f32[2]{0} fusion(f32[2]{0} %all-gather.1), kind=kLoop',
     None),
    # a fusion that calls a computation holding a collective is one
    ('%all-reduce-scatter (input: f32[8]) -> f32[2] {\n'
     '  %input = f32[8]{0} parameter(0)\n'
     '  %all-reduce.3 = f32[8]{0} all-reduce(%input), to_apply=%add\n'
     '  ROOT %dynamic-slice.1 = f32[2]{0} dynamic-slice(%all-reduce.3, %c)\n'
     '}\n\nENTRY %main (p: f32[8]) -> f32[2] {\n'
     '  %fusion.18 = f32[2]{0:T(8,128)} fusion(%p), kind=kCustom, '
     'calls=%all-reduce-scatter, metadata={op_name="jit(f)/attention/dot"}\n'
     '  ROOT %fusion.19 = f32[2]{0} fusion(%fusion.18), kind=kLoop, '
     'calls=%fused_computation\n}', "all-reduce.3 fusion.18")])
def test_collective_names_by_opcode(text, name):
    assert S.collective_names(text) == set(name.split() if name else ())


@pytest.mark.parametrize("op_name,want", [
    (AG + "/ppermute", ("tp.layer0.mlp.ag", "mlp", False)),
    (RS + "/all_gather", ("tp.layer0.mlp.rs", "mlp", True)),
    # the innermost site and the innermost component win
    ("jit(f)/mlp/site:a/attention/site:b/dot_general",
     ("b", "attention", False)),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "layer_params/dynamic_slice", (None, "layer_params", True)),
    # the primitive at the end of the path is no scope
    ("jit(f)/jvp()/mlp", (None, None, False)),
])
def test_scope_of(op_name, want):
    assert S.scope_of(op_name) == want


@pytest.mark.parametrize("check", ["partition", "site_exposed", "directions",
                                   "metrics", "no_scopes"])
def test_scoped_synthetic(check):
    r = S.scoped(SCOPED, NAMES, COLLECTIVES)
    d = r["devices"]["/device:TPU:0"]
    ns = 1e-9
    if check == "partition":
        # components, collectives, and the unscoped time add up to busy
        assert r["steps"] == 1
        assert d["busy_s"] == pytest.approx(48 * ns)
        assert d["busy_s"] == pytest.approx(
            TR.reduce(SCOPED)["devices"]["/device:TPU:0"]["busy_s"])
        parts = sum(d["compute_s"].values()) + sum(d["collective_s"].values())
        assert parts == pytest.approx(d["busy_s"])
        assert d["compute_s"]["attention"] == pytest.approx(10 * ns)
        assert d["compute_s"]["mlp"] == pytest.approx(18 * ns)
        assert d["compute_s"]["optimizer"] == pytest.approx(5 * ns)
        # fusion.10 starts under fusion.8: the overlap stays with fusion.8
        assert d["compute_s"]["loss"] == pytest.approx(1 * ns)
        assert d["compute_s"]["none"] == pytest.approx(2 * ns)
        assert d["collective_s"]["mlp"] == pytest.approx(9 * ns)
        assert d["collective_s"]["attention"] == pytest.approx(3 * ns)
        assert d["unscoped_s"] == pytest.approx(2 * ns)
    elif check == "site_exposed":
        ag = d["sites"]["tp.layer0.mlp.ag"]["fwd"]
        assert ag["collective_s"] == pytest.approx(11 * ns)    # 20..31
        assert ag["exposed_s"] == pytest.approx(3 * ns)  # 20..22, 30..31
        assert ag["ops"] == 1                  # the start, not the done
        # GSPMD's all-reduce under attention is exposed, but is no site's
        assert d["site_exposed_s"] == pytest.approx(9 * ns)
        assert d["site_exposed_s"] < \
            TR.reduce(SCOPED)["devices"]["/device:TPU:0"]["exposed_s"]
    elif check == "directions":
        assert {s: set(w) for s, w in d["sites"].items()} == {
            "tp.layer0.mlp.ag": {"fwd"}, "tp.layer0.mlp.rs": {"fwd", "bwd"}}
        rs = S.per_site(r)["tp.layer0.mlp.rs"]["bwd"]
        assert rs == pytest.approx({"collective_ms": 4e-6,
                                    "exposed_ms": 4e-6, "ops": 1})
    elif check == "metrics":
        want = {"attention_ms.train": 10e-6, "mlp_ms.train": 18e-6,
                "loss_ms.train": 1e-6, "optimizer_ms.train": 5e-6,
                "site_exposed_ms.train": 9e-6,
                "unscoped_share.train": 100 * 2 / 48,
                "embed_ms.train": None, "layer_params_ms.train": None}
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(S, "read", lambda ctx: r)
            got = {m: load_module(BENCH, "metrics", m + ".py").read({})
                   for m in want}
        assert got == pytest.approx(want)
    else:
        # a program without scopes, as before they were put in: nothing
        # to read, and no reader raises
        assert S.scoped(SCOPED, {"fusion.1": "jit(f)/dot_general"}) == {}
        for m in ("attention_ms.train", "site_exposed_ms.train",
                  "unscoped_share.train"):
            assert load_module(BENCH, "metrics", m + ".py").read(
                {"scoped": {}}) is None


@pytest.fixture(scope="module")
def yi_excerpt():
    with gzip.open(YI_EXCERPT, "rt") as f:
        events = json.load(f)
    return events, S.scoped(events, events["op_names"],
                             events["collectives"])


@pytest.mark.parametrize("check", ["sites", "partition", "unscoped"])
def test_recorded_yi_excerpt(yi_excerpt, check):
    events, r = yi_excerpt
    assert r["steps"] == 1 and len(r["devices"]) == 2
    busy = TR.reduce(events)["devices"]
    for dev, d in r["devices"].items():
        if check == "sites":
            assert {(s, w) for s, ways in d["sites"].items() for w in ways} \
                == {(f"tp.layer{i}.mlp.{k}", w) for i in (0, 1)
                    for k in ("ag", "rs") for w in ("fwd", "bwd")}
            assert 0 < d["site_exposed_s"] < busy[dev]["exposed_s"]
        elif check == "partition":
            assert d["busy_s"] == pytest.approx(busy[dev]["busy_s"])
            assert sum(d["compute_s"].values()) + sum(
                d["collective_s"].values()) == pytest.approx(d["busy_s"])
        else:
            assert d["unscoped_s"] / d["busy_s"] < 0.10
