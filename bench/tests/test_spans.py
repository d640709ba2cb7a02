"""The program's spans and scopes read back from a trace: on hand-made
events, on the compiled HLO of a CPU run, and on a recorded TPU trace cut
to a few steps of each cell (``data/tpu_program_spans.json``)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import spans
from run import reader

DATA = Path(__file__).parent / "data"
MS = 1e6
TPU = "/device:TPU:0"
CACHE = (640, 36, 64)


def _events():
    """A 100 ms window: a decode step whose device work ends at 50 ms with
    a bubble at 48 ms, the logits' sync from 50 to 58 ms, then the next
    step's dispatch and device work."""
    path = "jit(serve_decode_step)/while/body/closed_call"
    return {
        "window": (0, 100 * MS),
        "spans": [("serve.step", 0, 60 * MS),
                  ("serve.decode", 0, 60 * MS),
                  ("serve.decode.wait", 1 * MS, 50 * MS),
                  ("serve.decode.sync", 50 * MS, 58 * MS),
                  ("serve.step", 60 * MS, 100 * MS),
                  ("serve.decode", 60 * MS, 100 * MS),
                  ("serve.decode.dispatch", 60 * MS, 62 * MS)],
        "ops": {TPU: [
            ("while", 0, 45 * MS, "serve_decode_step", "", ()),
            ("fusion", 0, 20 * MS, "serve_decode_step",
             f"{path}/attention/dot_general", (8, 36, 640)),
            ("pad", 20 * MS, 25 * MS, "serve_decode_step",
             f"{path}/mlp/jit(_dequant_matmul_packed)/packed_matmul/pad",
             (128, 2304)),
            ("copy", 25 * MS, 40 * MS, "serve_decode_step", "",
             (40, 8, 640, 36, 64)),
            ("copy", 40 * MS, 45 * MS, "serve_decode_step", "",
             (1, 2304, 2880)),
            ("fusion", 45 * MS, 48 * MS, "cache_write_slot", "", (8,)),
            ("fusion", 49 * MS, 50 * MS, "serve_decode_step",
             f"{path}/lm_head/dot_general", (8, 122753)),
            ("fusion", 59 * MS, 100 * MS, "serve_decode_step",
             f"{path}/kv_cache/scatter", (40, 8, 640, 36, 64))]},
    }


def test_idle_is_charged_to_the_innermost_serve_span():
    r = spans.reduce(_events(), CACHE)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.090)
    # [48, 49] inside the wait; [50, 59] around 54.5 ms, in the sync
    assert r["idle"] == pytest.approx({"serve.decode.wait": 0.001,
                                       "serve.decode.sync": 0.009})
    assert r["span_count"]["serve.decode"] == 2
    assert r["span_s"]["serve.decode.sync"] == pytest.approx(0.008)


def test_ops_are_charged_to_their_innermost_scope_or_the_cache_shape():
    r = spans.reduce(_events(), CACHE)
    assert r["scopes"] == pytest.approx({
        "attention": 0.020, "packed_matmul": 0.005, "lm_head": 0.001,
        # the cache-shaped copy, the graft program, the step's cache write
        "kv_cache": 0.015 + 0.003 + 0.041,
        "unscoped": 0.005})                   # the weights' copy
    assert spans.engine_share(r) == pytest.approx(1.0)
    # without the cache's shape, the copy has no scope
    assert spans.reduce(_events())["scopes"]["unscoped"] == \
        pytest.approx(0.020)


def test_a_program_without_spans_or_scopes_gives_empty_tables():
    ev = _events()
    ev["spans"] = []
    ev["ops"] = {TPU: [op[:4] + ("",) + op[5:] for op in ev["ops"][TPU]
                       if op[3] != "cache_write_slot"]}
    r = spans.reduce(ev)
    assert set(r["idle"]) == {"host.other"}
    assert set(r["scopes"]) == {"unscoped"}
    assert spans.reduce(dict(ev, window=None)) is None


def test_instruction_keys_match_between_trace_and_compiled_hlo():
    event = ("%fusion.80 = s32[8]{0:T(128)S(1)} fusion(s32[8,1]{0,1:T(1,"
             "128)} %tok.1), kind=kLoop, calls=%fused_computation.160")
    compiled = ("  ROOT %fusion.80 = s32[8]{0:T(128)S(1)} fusion(%tok.1), "
                "kind=kLoop, calls=%fused_computation.160, metadata={op_name"
                "=\"jit(serve_decode_step)/kv_cache/add\" stack_frame_id=3}")
    assert spans.instruction_key(event) == "fusion.80 s32[8]{0:T(128)S(1)}"
    paths = spans.op_paths([compiled])
    assert paths[spans.instruction_key(event)] == \
        "jit(serve_decode_step)/kv_cache/add"
    assert paths["fusion.80"] == "jit(serve_decode_step)/kv_cache/add"
    assert spans.output_dims(
        "%copy.5 = bf16[40,8,640,36,64]{2,4,3,1,0} copy(...)") == \
        (40, 8, 640, 36, 64)
    assert spans.output_dims("%copy-start = (f32[1,2304]{1,0}, f32[1,2304]"
                             "{1,0}, u32[]) copy-start(...)") == (1, 2304)
    assert spans.module_name("jit_serve_decode_step(169007725162)") == \
        "serve_decode_step"


def test_compiled_texts_are_the_programs_that_ran(tiny_spec, tmp_path):
    """On a CPU run through the harness, the reader's programs come from
    JAX's cache (nothing compiles again) and carry every scope."""
    import harness
    from conftest import TINY_DECODE
    from weights import program_params
    clock = harness.CompileClock()
    eng = harness.build_engine(tiny_spec, program_params(tiny_spec, 3))
    rec = harness.Recorder(eng)
    harness.warm(eng, tiny_spec)
    w = harness.drive(eng, rec, tiny_spec, TINY_DECODE, 3, 2.0, clock,
                      tmp_path / "trace")
    run = harness.RunView(spec=tiny_spec, cell={"name": "tiny"},
                          mix=TINY_DECODE, window=w, rec=rec, setup_s=0.0,
                          trace=None, peak={})
    compiles = clock.compiles
    texts = spans.compiled_texts(run)
    assert clock.compiles == compiles
    chunks = {(d.rows, d.tokens) for d in run.traced_dispatches()
              if d.kind == "chunk"}
    assert len(texts) == 1 + len(chunks)
    assert texts[0].startswith("HloModule jit_serve_decode_step")
    paths = spans.op_paths(texts)
    assert {p for v in paths.values() for p in v.split("/")} \
        >= set(spans.SCOPES)


def _recorded(cell):
    """One cell of the recorded trace: ``events`` for ``reduce``."""
    c = json.loads((DATA / "tpu_program_spans.json").read_text())[cell]
    ops = [(c["labels"][lab], s, e, c["modules"][m], c["paths"][p],
            tuple(dims)) for lab, s, e, m, p, dims in c["ops"]]
    return {"window": tuple(c["window"]),
            "spans": [tuple(sp) for sp in c["spans"]], "ops": {TPU: ops}}


def _plain_scope_seconds(events, scope):
    """Device seconds of the ops whose innermost known scope is ``scope``,
    counted without the reader's code."""
    lo, hi = events["window"]
    total = 0.0
    for label, s, e, _, path, _ in events["ops"][TPU]:
        names = [p for p in path.split("/") if p in spans.SCOPES]
        if label != "while" and names and names[-1] == scope:
            total += (min(e, hi) - max(s, lo)) * 1e-9
    return total


@pytest.mark.parametrize("cell,gap", [
    ("minicpm-2b.int4.decode", "serve.decode"),
    ("minicpm-2b.int4.chat", "serve.admit")])
def test_recorded_idle_lies_in_the_host_span_that_caused_it(cell, gap):
    r = spans.reduce(_recorded(cell), CACHE)
    idle = sum(r["idle"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["idle"].get("host.other", 0.0) == 0.0
    # the gap between the device's last op and the next dispatch is the
    # host's: the decode's wait, sync and commit in decode, the row's first
    # token in chat (the device's clock lies within a few ms of the host's,
    # so the gap's midpoint can fall on a neighbouring span of the tree)
    assert sum(v for k, v in r["idle"].items()
               if k.startswith(gap)) > 0.75 * idle
    assert spans.engine_share(r) == pytest.approx(1.0)


@pytest.mark.parametrize("cell", ["minicpm-2b.int4.decode",
                                  "minicpm-2b.int4.chat"])
def test_recorded_ops_are_charged_to_scopes_and_the_cache_shape(cell):
    events = _recorded(cell)
    r = spans.reduce(events, CACHE)
    for scope in ("attention", "packed_matmul", "lm_head"):
        assert r["scopes"][scope] == pytest.approx(
            _plain_scope_seconds(events, scope))
    # the cache's own ops, and the copies of its leaves that XLA inserts
    # for the layer scan's slices and writes, which carry no known scope
    copies = [op for op in events["ops"][TPU]
              if op[0] == "copy" and op[5][-3:] == CACHE
              and not set(op[4].split("/")) & set(spans.SCOPES)]
    assert copies
    assert r["scopes"]["kv_cache"] > _plain_scope_seconds(events,
                                                          "kv_cache")
    # the packed kernel runs under the packed_matmul scope
    kernel = [op for op in events["ops"][TPU]
              if op[0] == "dequant_matmul_packed_int4"]
    assert kernel and all("/packed_matmul/" in op[4] for op in kernel)
    assert r["scopes"]["unscoped"] < 0.1 * sum(r["scopes"].values())


def test_readers_on_the_recorded_trace(monkeypatch):
    events = _recorded("minicpm-2b.int4.decode")
    reduced = spans.reduce(events, CACHE)
    monkeypatch.setattr(spans, "for_run", lambda run, root: reduced)
    # the window holds one step's tail: 8 rows of one token each
    run = SimpleNamespace(traced_dispatches=lambda: [
        SimpleNamespace(kind="step", rows=8, tokens=1)])
    bench = Path(__file__).resolve().parents[1]
    sync = [sp for sp in events["spans"] if sp[0] == "serve.decode.sync"]
    assert reader("host_sync_ms", bench)(run) == pytest.approx(
        1e3 * (sync[0][2] - sync[0][1]) * 1e-9)
    assert reader("attention_us_per_tok", bench)(run) == pytest.approx(
        1e6 * _plain_scope_seconds(events, "attention") / 8)
    assert reader("kv_cache_us_per_tok", bench)(run) == pytest.approx(
        1e6 * reduced["scopes"]["kv_cache"] / 8)
    # a trace without the program's spans or scopes gives nothing
    bare = dict(events, spans=[], ops={TPU: [
        op[:4] + ("",) + op[5:] for op in events["ops"][TPU]]})
    monkeypatch.setattr(spans, "for_run",
                        lambda run, root: spans.reduce(bare, CACHE))
    for name in ("host_sync_ms", "attention_us_per_tok",
                 "kv_cache_us_per_tok"):
        assert reader(name, bench)(run) is None
