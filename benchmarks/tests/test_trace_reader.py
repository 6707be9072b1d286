"""The reduction from a trace to busy and idle time, per-operation time and
gap attribution: on hand-made intervals, and on a trace recorded on the chip
(three 2-step dispatches of gpt_tiny, `data/tiny_train.xplane.pb.gz`)."""
import os

import pytest

from benchmarks import trace_reader as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_train.xplane.pb.gz")


def test_merge_covers_overlaps_and_nesting():
    events = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("nested", 31, 2),
              ("zero", 50, 0)]
    assert tr.merge(events) == [[0, 15], [30, 35]]
    merged = tr.merge(events)
    assert tr.covered(merged, 0, 40) == 20
    assert tr.covered(merged, 10, 32) == 5 + 2
    assert tr.gaps(merged, 0, 40) == [(15, 30), (35, 40)]
    assert tr.gaps(merged, 12, 33) == [(15, 30)]


def test_self_time_takes_children_out_of_a_loop():
    events = [("while", 0, 100), ("body", 10, 30), ("inner", 15, 5),
              ("body", 50, 30), ("after", 120, 10)]
    assert tr.totals(events) == {"while": 40, "body": 55, "inner": 5,
                                 "after": 10}
    assert tr.totals(events, self_time=False)["while"] == 100


def test_gap_goes_to_the_host_span_that_covers_most_of_it():
    spans = [("dispatch", 0, 40), ("wait", 40, 100), ("client_wait", 0, 500)]
    assert tr.attribute((10, 30), spans, ignore=("client_wait",)) \
        == "dispatch"
    assert tr.attribute((30, 100), spans, ignore=("client_wait",)) == "wait"
    assert tr.attribute((200, 300), spans, ignore=("client_wait",)) \
        == "engine_internal"
    assert tr.attribute((200, 300), spans) == "client_wait"


def test_reduce_window_on_a_hand_made_trace():
    trace = {"devices": [{"name": "/device:TPU:0", "modules": [],
                          "ops": [("%a fusion", 0, 10), ("%b fusion", 20, 10),
                                  ("%a fusion", 40, 10)]}],
             "host_spans": [("dispatch", 8, 14)]}
    red = tr.reduce_window(trace)
    assert red["window_s"] == pytest.approx(50e-9)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["device_ops"] == [["a fusion", pytest.approx(20e-9)],
                                 ["b fusion", pytest.approx(10e-9)]]
    assert dict(map(tuple, red["idle_gaps"])) == {
        "dispatch": pytest.approx(10e-9),
        "engine_internal": pytest.approx(10e-9)}
    assert tr.reduce_window({"devices": [], "host_spans": []}) == {}


def test_names_are_cut_from_the_hlo_text():
    text = ("%while.4 = (s32[]{:T(128)}, f32[64]{0:T(128)S(1)}) "
            "while((s32[]{:T(128)}, f32[64]{0:T(128)}) %tuple.1)")
    assert tr.op_name(text) == "%while.4 while"
    assert tr.op_name("%jvp_jit__unknown___.223 = (bf16[16,128,4,16]{3,2,1,0"
                      ":T(8,128)(2,1)}) custom-call(bf16[4] %x)") \
        == "%jvp_jit__unknown___.223 custom-call"
    assert tr.module_name("jit_multi(1438235303381955233)") == "jit_multi"
    assert tr.family("%fusion.12 fusion") == tr.family("%fusion fusion") \
        == "fusion"
    assert tr.family("%jvp_jit__unknown___.223 custom-call") \
        == "jvp_jit__unknown___ custom-call"


def test_recorded_chip_trace():
    trace = tr.load(DATA)
    assert [d["name"] for d in trace["devices"]] == ["/device:TPU:0"]
    dev = trace["devices"][0]
    assert len(dev["ops"]) == 2241
    assert sum(1 for n, _, _ in dev["modules"] if n == "jit_multi") == 3
    # three dispatches x 2 steps x 2 layers x (fwd, dq, dkv) Mosaic kernels
    kernels = [n for n, _, _ in dev["ops"] if n.endswith(" custom-call")]
    assert len(kernels) >= 36
    assert [s[0] for s in trace["host_spans"]] == ["dispatch", "wait"] * 3
    win = tr.main_module_window(trace)
    assert (win["module"], win["launches"]) == ("jit_multi", 3)
    red = tr.reduce_window(trace, win["lo"], win["hi"])
    assert 0 < red["busy_s"] < red["window_s"]
    # the tiny step leaves the chip idle while the host dispatches
    assert red["idle_gaps"][0][0] == "dispatch"
    assert red["busy_s"] / red["window_s"] < 0.2
    # the launched programs first, then the families of operations
    assert red["device_ops"][0][0] == "program jit_multi"
    assert len(red["device_ops"]) == 10
    assert "transpose_jvp_jit__unknown____ custom-call" in dict(
        map(tuple, red["device_ops"]))
    total_self = sum(tr.totals(tr.within(dev["ops"], win["lo"],
                                         win["hi"])).values())
    assert total_self <= tr.covered(tr.merge(dev["ops"]), win["lo"],
                                    win["hi"]) * 1.001 + 1


def test_the_flash_kernels_are_found_by_the_families_the_mix_names():
    import pytest

    from benchmarks import harness

    train = harness.load_module(harness.os.path.join(
        harness.HERE, "drivers", "train.py"), "d_train_scope")
    trace = tr.load(DATA)
    mix = harness.load_json(harness.HERE, "traffic",
                            "pretrain_b16s1024.json")
    scope = train.trace_scope({"trace": trace, "mix": mix})
    win = tr.main_module_window(trace)
    ops = tr.within(trace["devices"][0]["ops"], win["lo"], win["hi"])
    by_family = {}
    for n, ns in tr.totals(ops).items():
        by_family[tr.family(n)] = by_family.get(tr.family(n), 0) + ns
    # fwd is one family, dq and dkv the other; the lone `custom-call` of
    # the step that is no flash kernel stays out
    assert scope["flash_s"] * 1e9 == pytest.approx(
        by_family["jvp_jit__unknown___ custom-call"]
        + by_family["transpose_jvp_jit__unknown____ custom-call"])
    assert "custom-call" in by_family
    # a step whose kernels go by another name is an error, not a silence
    with pytest.raises(RuntimeError, match="no operation of the families"):
        train.trace_scope({"trace": trace, "mix": {
            "trace_names": {"flash": ["paged_attention* custom-call"]}}})
    serve = harness.load_module(harness.os.path.join(
        harness.HERE, "drivers", "serve.py"), "d_serve_scope")
    with pytest.raises(RuntimeError, match="none of the decode programs"):
        serve.trace_scope({"trace": trace, "mix": {
            "trace_names": {"decode": ["jit_step"]}}})
