"""Per-layer step time read from the program's scopes in a trace: on a
hand-built trace with known answers, and on traces recorded on a v5e."""
import pathlib
import re
import shutil

import pytest

from chipbench import core, scopes, trace

DATA = core.HERE / "tests" / "data" / "trace"
MS_METRICS = ("prf_attention_ms.train", "attn_proj_ms.train",
              "mlp_ms.train", "vocab_ms.train", "optimizer_ms.train")
METRICS = MS_METRICS + ("unscoped_share.train",)
US = 1_000_000           # picoseconds in a microsecond
LINE_NS = 500            # the XLA Ops line's own timestamp


def _read(name, ctx):
    return core.load_module("metrics", name).read(ctx)


def _write_trace(path: pathlib.Path, tail: bool = False):
    """A device plane with three executions of the step program (the
    third cut by the window's end, [1000, 10000) us, and still open when
    the capture stops unless ``tail`` adds a later event), a smaller
    program between them, a loop that holds others, and an op with no
    name."""
    space = scopes.xspace_class()()
    plane = space.planes.add(name=scopes.DEVICE)
    plane.stat_metadata[1].name = "tf_op"
    # a name kept once in the stat metadata, referred to by id
    plane.stat_metadata[9].name = \
        "jit(train_step)/transpose(jvp(prf_mix))/dot_general:"
    meta = {}

    def metadata(name, tf_op=None, ref=None):
        k = len(meta) + 1
        m = plane.event_metadata[k]
        m.name = name
        if tf_op is not None:
            m.stats.add(metadata_id=1, str_value=tf_op)
        if ref is not None:
            m.stats.add(metadata_id=1, ref_value=ref)
        meta[name] = k
        return k

    step = metadata("jit_train_step(7)")
    other = metadata("jit_convert_element_type(3)")
    mods = plane.lines.add(name="XLA Modules", timestamp_ns=0)
    runs = [(step, 1000, 3000), (other, 3100, 3200), (step, 4000, 6000),
            (step, 9000, 11000)] + [(other, 11500, 11600)] * tail
    for mid, s, t in runs:
        mods.events.add(metadata_id=mid, offset_ps=s * US,
                        duration_ps=(t - s) * US)
    path_of = "jit(train_step)/jvp()/while/body/closed_call"
    ops = [  # (name, tf_op or ref, start us, end us)
        ("%while.1 = (s32[]) while((s32[]) %t)",
         "jit(train_step)/jvp()/while", 1000, 2900),
        ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)",
         f"{path_of}/prf_mix/dot_general:", 1100, 1400),
        ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %a)",
         "jit(train_step)/jvp(mlp)/add:", 1400, 1600),
        ("%fusion.3 = f32[4]{0} fusion(f32[4]{0} %a)", 9, 1600, 2100),
        ("%convert.4 = bf16[4]{0} convert(f32[4]{0} %a)", None, 2100,
         2200),
        ("%fusion.5 = f32[4]{0} fusion(f32[4]{0} %a)",
         "jit(train_step)/optimizer/mul:", 2900, 3000),
        ("%convert.6 = bf16[4]{0} convert(f32[4]{0} %b)", None, 3100,
         3200),
        ("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %a)",
         f"{path_of}/checkpoint/prf_features/bghld,grd->bghlr/"
         "dot_general:", 4100, 4300),
        ("%fusion.8 = f32[4]{0} fusion(f32[4]{0} %a)",
         f"{path_of}/attn_in/dot_general:", 4300, 4400),
        ("%fusion.9 = f32[4]{0} fusion(f32[4]{0} %a)",
         f"{path_of}/attn_out/dot_general:", 4400, 4450),
        ("%fusion.10 = f32[4]{0} fusion(f32[4]{0} %a)",
         "jit(train_step)/transpose(jvp(embed))/scatter-add:", 4500,
         4600),
        ("%fusion.11 = f32[4]{0} fusion(f32[4]{0} %a)",
         "jit(train_step)/jvp(lm_head)/dot_general:", 4600, 4900),
        ("%fusion.12 = f32[4]{0} fusion(f32[4]{0} %a)",
         "jit(train_step)/jvp(loss)/reduce_max:", 4900, 5000),
        ("%fusion.13 = f32[4]{0} fusion(f32[4]{0} %a)",
         f"{path_of}/prf_mix/dot_general:", 9100, 9500),
    ]
    line = plane.lines.add(name="XLA Ops", timestamp_ns=LINE_NS)
    for name, op, s, t in ops:
        k = metadata(name, ref=op) if isinstance(op, int) else \
            metadata(name, tf_op=op)
        line.events.add(metadata_id=k, offset_ps=s * US - LINE_NS * 1000,
                        duration_ps=(t - s) * US)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space.SerializeToString())


@pytest.fixture
def toy(tmp_path):
    _write_trace(tmp_path / "plugins" / "profile" / "1" / "h.xplane.pb")
    return {"trace_dir": tmp_path, "span_ns": (1000e3, 10000e3)}


def test_events_read_with_their_names(toy):
    lines = scopes.device_lines(trace.xplane_file(toy["trace_dir"]))
    ops = lines["XLA Ops"]
    assert ops[1].start == pytest.approx(1100e3)
    assert ops[1].end == pytest.approx(1400e3)
    assert ops[3].tf_op.endswith("transpose(jvp(prf_mix))/dot_general:")
    assert ops[4].tf_op is None
    assert [e.name for e in lines["XLA Modules"]].count(
        "jit_train_step(7)") == 3


@pytest.mark.parametrize("path, scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/prf_mix/dot_general:",
     "prf_mix"),
    ("jit(train_step)/jvp(mlp)/add:", "mlp"),
    ("jit(train_step)/transpose(jvp(prf_mix))/dot_general", "prf_mix"),
    ("jit(train_step)/mlp/x/prf_mix/add:", "prf_mix"),
    ("jit(train_step)/jvp()/while", scopes.UNSCOPED),
    ("params['embed']:", scopes.UNSCOPED),
    ("jit(train_step)/jvp(embedding)/gather:", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_scope_is_the_innermost_path_component(path, scope):
    assert scopes.scope_of(path) == scope


@pytest.mark.parametrize("name, value", [
    # two whole executions; the third, cut by the window, and the other
    # program are left out; the loop is left out, the nameless convert
    # is unscoped
    ("prf_attention_ms.train", (300 + 500 + 200) / 2 / 1e3),
    ("attn_proj_ms.train", (100 + 50) / 2 / 1e3),
    ("mlp_ms.train", 200 / 2 / 1e3),
    ("vocab_ms.train", (100 + 300 + 100) / 2 / 1e3),
    ("optimizer_ms.train", 100 / 2 / 1e3),
    ("unscoped_share.train", 100 * 100 / 2050),
])
def test_each_metric_reads_its_known_value(toy, name, value):
    assert _read(name, toy) == pytest.approx(value)


def test_scopes_and_unscoped_add_up_to_the_step(toy):
    per_step = sum(_read(m, toy) for m in MS_METRICS)
    share = _read("unscoped_share.train", toy) / 100
    assert per_step / (1 - share) == pytest.approx(2050 / 2 / 1e3)


@pytest.mark.parametrize("tail, value", [
    (False, (300 + 500 + 200) / 2 / 1e3),       # third still open
    (True, (300 + 500 + 200 + 400) / 3 / 1e3),  # third whole
])
def test_an_execution_open_when_the_capture_stops_is_left_out(
        tmp_path, tail, value):
    _write_trace(tmp_path / "h.xplane.pb", tail)
    ctx = {"trace_dir": tmp_path, "span_ns": (1000e3, 20000e3)}
    assert _read("prf_attention_ms.train", ctx) == pytest.approx(value)


def test_a_window_without_the_step_reads_nothing(toy):
    ctx = dict(toy, span_ns=(12000e3, 13000e3))
    assert all(_read(m, ctx) is None for m in METRICS)


def test_recorded_ops_match_profile_data():
    """The events of the unscoped recording, read from the file itself,
    match ``jax.profiler.ProfileData``'s one for one (it keeps whole
    nanoseconds)."""
    from jax.profiler import ProfileData
    path = str(DATA / "tiny-finetune.xplane.pb")
    ours = scopes.device_lines(path)["XLA Ops"]
    theirs = [e for p in ProfileData.from_file(path).planes
              if p.name == scopes.DEVICE for ln in p.lines
              if ln.name == "XLA Ops" for e in ln.events]
    assert len(ours) == len(theirs) > 1000
    for a, b in zip(ours, theirs):
        assert a.name == b.name
        assert a.start == pytest.approx(b.start_ns, abs=1.0)
        assert a.end - a.start == pytest.approx(b.duration_ns, abs=1.0)


def _recorded(tmp_path, name):
    """A traced run's reading context for one recorded trace."""
    shutil.copy(DATA / name, tmp_path / name)
    return {"trace_dir": tmp_path,
            "span_ns": trace.load(tmp_path).span_bounds()}


def test_a_recording_without_scopes_is_all_unscoped(tmp_path):
    """The first recording predates the program's scopes: every op of
    the step is unscoped, and no layer reads a number."""
    ctx = _recorded(tmp_path, "tiny-finetune.xplane.pb")
    assert _read("unscoped_share.train", ctx) == 100.0
    assert all(_read(m, ctx) is None for m in MS_METRICS)


def test_a_scoped_recording_reads_every_layer(tmp_path):
    """A recording of the toy cell with the program's scopes: every
    metric reads, and what stays unscoped is no op of a model layer but
    the layer loop's own slicing and stacking, the sum of its auxiliary
    losses, copies of the step's arguments, and ops XLA made without a
    name (the f32 weights' bf16 casts it hoists out of the loop)."""
    ctx = _recorded(tmp_path, "tiny-finetune-scoped.xplane.pb")
    values = {m: _read(m, ctx) for m in METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    ops, runs = scopes.step_ops(
        trace.xplane_file(tmp_path), *ctx["span_ns"])
    assert runs > 10
    left = {op.tf_op for op in ops
            if scopes.scope_of(op.tf_op) == scopes.UNSCOPED}
    assert None in left
    machinery = re.compile(
        r"jit\(train_step\)/(jvp\(\)|transpose\(jvp\(\)\))/"
        r"(while(/body/dynamic_(update_)?slice)?|reduce_sum|"
        r"broadcast_in_dim):")
    assert all(n is None or machinery.fullmatch(n)
               or n.startswith(("params[", "opt_state[", "batch["))
               for n in left), left
