"""The trace reduction: busy union, idle gaps, per-op and per-program
device time, and gaps labelled by the engine's host spans."""
import pytest

from chipbench import trace

F1 = "%fusion.1 = bf16[16,128]{1,0} fusion(bf16[16,128]{1,0} %p), kind=kLoop"
F2 = "%fusion.2 = bf16[16,128]{1,0} fusion(bf16[16,128]{1,0} %q), kind=kLoop"
F3 = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %r), kind=kLoop"
PAGED = ("%closed_call.157 = (f32[128,8,128]{2,1,0}, f32[128,8,1]{2,1,0}, "
         "f32[128,8,1]{2,1,0}) custom-call(s32[16,32]{1,0} %bt, "
         "bf16[1400,512,1024]{2,1,0} %k), "
         'custom_call_target="tpu_custom_call"')
LINEAR = ("%closed_call.150 = bf16[16,9216]{1,0} custom-call(bf16[16,7168]"
          "{1,0} %x, s8[7168,9216]{1,0} %w, f32[56,9216]{1,0} %s), "
          'custom_call_target="tpu_custom_call"')
LOOP = ("%while.78 = (s32[]{:T(128)}, bf16[16,128]{1,0}) while((s32[], "
        "bf16[16,128]) %tuple.91), condition=%c, body=%b")

# window [1000, 2000) ns; ops on chip 0 overlap, one straddles the start,
# and a loop holds them (its time is its ops')
EX = {
    "window_ns": (1000.0, 2000.0),
    "chips": [
        {"ops": [(LOOP, 1000.0, 800.0), (F1, 900.0, 200.0),
                 (F2, 1150.0, 100.0), (PAGED, 1200.0, 200.0),
                 (LINEAR, 1700.0, 100.0)],
         "modules": [("jit_loop_fn", 1100.0, 500.0),
                     ("jit__prefill_paged_fn", 1650.0, 300.0),
                     ("jit_loop_fn", 2500.0, 10.0)]},
        {"ops": [(F3, 1000.0, 1000.0)], "modules": []},
    ],
}


def test_reduce_fixed_numbers():
    red = trace.reduce(EX)
    # chip 0 busy: [1000,1100) + [1150,1400) + [1700,1800) = 450 ns;
    # chip 1 busy the whole 1000 ns; averaged over the chips
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["busy_s"] == pytest.approx(725e-9)
    assert red["idle_share"] == pytest.approx(0.275)
    # ops that start inside the window, by their HLO text; not the loop
    assert red["ops"] == pytest.approx(
        {F2: 100e-9, F3: 1000e-9, PAGED: 200e-9, LINEAR: 100e-9})
    assert red["modules"] == pytest.approx(
        {"jit_loop_fn": 500e-9, "jit__prefill_paged_fn": 300e-9})
    assert red["module_runs"] == {"jit_loop_fn": 1, "jit__prefill_paged_fn": 1}
    gaps = [(round(s * 1e9), round(d * 1e9)) for s, d in red["gaps"]]
    assert gaps == [(100, 50), (400, 300), (800, 200)]


def test_no_window_or_no_device_gives_nothing():
    assert trace.reduce({"window_ns": None, "chips": EX["chips"]}) is None
    assert trace.reduce({"window_ns": (0.0, 1.0), "chips": []}) is None


def test_opcodes_and_labels():
    assert trace.opcode(LOOP) == "while" and trace.is_container(LOOP)
    assert trace.opcode(PAGED) == "custom-call"
    assert trace.opcode(F1) == "fusion" and not trace.is_container(F1)
    assert trace.op_label(F1) == "fusion"
    assert trace.op_label(PAGED) == "tpu_custom_call closed_call"
    red = trace.reduce(EX)
    by = trace.by_label(red["ops"], trace.op_label)
    assert by == pytest.approx({"fusion": 1100e-9,
                                "tpu_custom_call closed_call": 300e-9})


def metric(name):
    """A per-layer metric's reader module, loaded as the harness loads
    it."""
    from chipbench import run

    return run.load_metric(run.ROOT, name)


def test_kernel_matchers_of_the_roofline_metrics():
    from chipbench.reference import Dims

    dims = Dims(16, 7168, 56, 8, 128, 19200, 32256, 1e-6, 1e5, False)
    paged = metric("paged_attention_roofline")
    linear = metric("fused_linear_roofline")
    assert paged.matches(PAGED, dims) and not paged.matches(LINEAR, dims)
    assert linear.matches(LINEAR, dims) and not linear.matches(PAGED, dims)
    head = LINEAR.replace("s8[7168,9216]", "s8[7168,32256]")
    assert not linear.matches(head, dims)
    assert not paged.matches(F1, dims) and not linear.matches(F1, dims)


def test_seconds_of_matches_substrings():
    table = {"jit_loop_fn": 2.0, "jit__prefill_paged_fn": 1.0, "x": 4.0}
    assert trace.seconds_of(table, ("loop_fn",)) == 2.0
    assert trace.seconds_of(table, ("prefill", "x")) == 5.0
    assert trace.seconds_of(table, ("nothing",)) == 0.0


def test_engine_spans_and_gap_labels():
    events = [
        {"ph": "M", "tid": 0, "name": "thread_name"},
        {"ph": "B", "tid": 0, "name": "step", "ts": 100.0},
        {"ph": "B", "tid": 0, "name": "dispatch", "ts": 110.0},
        {"ph": "E", "tid": 0, "name": "dispatch", "ts": 120.0},
        {"ph": "B", "tid": 5, "name": "request", "ts": 111.0},
        {"ph": "B", "tid": 0, "name": "sync", "ts": 130.0},
        {"ph": "E", "tid": 0, "name": "sync", "ts": 190.0},
        {"ph": "E", "tid": 0, "name": "step", "ts": 200.0},
    ]
    spans = trace.engine_spans(events, offset_us=100.0)
    assert sorted((round(s * 1e6), round(e * 1e6), n, d)
                  for s, e, n, d in spans) == [
        (0, 100, "step", 0), (10, 20, "dispatch", 1), (30, 90, "sync", 1)]
    labels = trace.label_gaps(
        [(12e-6, 2e-6), (20e-6, 8e-6), (50e-6, 1e-6), (150e-6, 5e-6)], spans)
    assert labels == [("dispatch", 2e-6), ("step", 8e-6), ("sync", 1e-6),
                      ("harness", 5e-6)]


def test_recorded_chip_trace_reduces_to_fixed_numbers():
    """60 ms of a traced window recorded on one TPU v5 lite (the
    deepseek-coder-33b-16l.code-decode cell, around the start of a
    prefill), in ``trace.extract``'s form."""
    import gzip
    import json
    import pathlib

    from chipbench.reference import Dims

    path = pathlib.Path(__file__).parent / "data" / "trace_slice.json.gz"
    sl = json.loads(gzip.decompress(path.read_bytes()))
    red = trace.reduce({"window_ns": tuple(sl["window_ns"]),
                        "chips": sl["chips"]})
    assert red["window_s"] == pytest.approx(0.06)
    assert red["busy_s"] == pytest.approx(0.059988225)
    assert red["idle_share"] == pytest.approx(1.9625e-4, rel=1e-6)
    assert len(red["gaps"]) == 381
    assert sum(d for _, d in red["gaps"]) == pytest.approx(1.1775e-05)
    assert red["modules"]["jit__prefill_paged_fn(15874618799230353826)"] \
        == pytest.approx(0.671771816)
    dims = Dims(16, 7168, 56, 8, 128, 19200, 32256, 1e-6, 1e5, False)
    paged = metric("paged_attention_roofline")
    linear = metric("fused_linear_roofline")
    assert sum(v for k, v in red["ops"].items()
               if paged.matches(k, dims)) == pytest.approx(0.003788199)
    assert sum(v for k, v in red["ops"].items()
               if linear.matches(k, dims)) == pytest.approx(0.058366565)


def test_every_reader_on_a_traced_window():
    """Each per-layer metric's reader, on a window shaped like a traced
    chip run of deepseek-coder-33b-16l: a share of a peak or roofline
    lies in (0, 100], and leaning-to-skip blocks lower the least time
    of the kernels that read their weights."""
    import types

    import numpy as np

    from chipbench import peaks, run, spec
    from chipbench.reference import Dims

    family = spec.load_family(run.ROOT, "dense_gqa")
    dims = Dims(16, 7168, 56, 8, 128, 19200, 32256, 1e-6, 1e5, False)
    lean = (np.array([True] * 12 + [False] * 4),
            np.array([True] * 12 + [False] * 4))
    epochs = [types.SimpleNamespace(n=8, residents=9, ctx_sum=9 * 600,
                                    entries=9 * 600 * 12)] * 20
    reqs = [types.SimpleNamespace(prompt=np.zeros(500, np.int32),
                                  times=[1.0 + i for i in range(40)])]
    snaps = [types.SimpleNamespace(residents=9, dispatched=True)] * 20
    red = {"window_s": 51.0, "busy_s": 50.5, "idle_share": 0.5 / 51.0,
           "ops": {LINEAR: 38.0, PAGED: 4.5, F1: 3.0},
           "modules": {"jit_loop_fn": 44.0, "jit__prefill_paged_fn": 5.0}}

    def ctx(lean_):
        return types.SimpleNamespace(
            family=family, dims=dims, facts={},
            peaks=peaks.peaks("TPU v5 lite"), slots=16,
            trace=red, epochs=epochs, prefills=[(600, 1024)] * 20,
            snaps=snaps, queue_waits=[1.0, 2.0, 3.0],
            counters={"kv_entries_dense_measured_total": 1600.0,
                      "kv_entries_stored_measured_total": 1200.0},
            reqs=reqs, t_open=0.0, t_close=51.0, keep=(0.75, 0.75),
            lean=lean_)

    shares = ("batch_occupancy", "mfu", "decode_step_roofline",
              "paged_attention_roofline", "fused_linear_roofline",
              "kv_saved_fraction", "idle_share")
    got = {n: metric(n).read(ctx(lean)) for n in shares}
    for name, v in got.items():
        assert v is not None and 0 < v <= 100, (name, v)
    assert metric("queue_wait_p50_ms").read(ctx(lean)) == 2000.0
    assert metric("prefill_ms_per_ktok").read(ctx(lean)) == pytest.approx(
        5.0 / 12000 * 1e6)
    every = tuple(np.ones(16, bool) for _ in range(2))
    for name in ("decode_step_roofline", "fused_linear_roofline"):
        assert got[name] < metric(name).read(ctx(every)), name
