"""The reduction from a trace to numbers: hand-worked intervals, then a
piece of a real v5e trace kept as a fixture."""

import importlib.util
import os

import pytest

from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "perfbench_cut_fixture", os.path.join(HERE, "cut_fixture.py"))
cut_fixture = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cut_fixture)
FIXTURE = os.path.join(HERE, "fixtures", "v5e_chat_open_250ms.xplane.pb")

# name, start_ns, duration_ns
OPS = [
    ("fusion.1", 0.0, 100.0),
    ("fusion.2", 50.0, 100.0),      # overlaps the first: busy 0..150
    ("copy.3", 150.0, 50.0),        # touches: busy 0..200
    ("fusion.1", 400.0, 100.0),     # gap 200..400
    ("fusion.2", 1000.0, 0.0),      # no duration: not busy
    ("custom-call.4", 600.0, 300.0),  # gap 500..600; busy to 900
]


def test_merge_intervals_is_a_union():
    assert tr.merge_intervals(OPS) == [(0.0, 200.0), (400.0, 500.0), (600.0, 900.0)]


def test_busy_window_and_gaps_by_hand():
    window, busy, gaps = tr.busy_and_gaps(OPS)
    assert window == 900.0
    assert busy == 200.0 + 100.0 + 300.0
    assert gaps == [(200.0, 200.0), (500.0, 100.0)]
    assert tr.busy_and_gaps([]) == (0.0, 0.0, [])


def test_by_name_and_top():
    named = tr.by_name(OPS)
    assert named["fusion.1"] == {"count": 2, "total_ns": 200.0, "median_ns": 100.0}
    assert tr.top(named, 2) == [["custom-call.4", 300.0 / 1e9], ["fusion.1", 200.0 / 1e9]]


def test_strip_hash():
    assert tr.strip_hash("jit__decode_impl(12345678901)") == "jit__decode_impl"
    assert tr.strip_hash("jit_f") == "jit_f"


def planes():
    return {
        "/host:CPU": {"python3": [("x", 0.0, 5.0)]},
        "/device:TPU:0": {
            "XLA Ops": [(n, s + 1000.0, d) for n, s, d in OPS],
            "XLA Modules": [
                ("jit__prefill_impl(11)", 1000.0, 200.0),
                ("jit__decode_impl(22)", 1400.0, 100.0),
                ("jit__decode_impl(22)", 1600.0, 300.0),
            ],
        },
    }


def test_reduce_planes_one_device():
    red = tr.reduce_planes(planes())
    assert [d["plane"] for d in red["devices"]] == ["/device:TPU:0"]
    dev = red["devices"][0]
    assert dev["window_s"] == pytest.approx(900e-9)
    assert dev["busy_s"] == pytest.approx(600e-9)
    assert dev["idle_pct"] == pytest.approx(100.0 * (1 - 600 / 900))
    assert dev["modules"]["jit__decode_impl"]["count"] == 2
    assert dev["modules"]["jit__decode_impl"]["median_ns"] == 200.0
    # times are kept from the first operation on
    assert dev["module_events"][0] == ["jit__prefill_impl", 0.0, 200.0]
    assert dev["gaps"] == [[200.0, 200.0], [500.0, 100.0]]
    assert red["structure"]["/host:CPU"] == {"python3": 1}


def test_gaps_are_named_by_the_programs_round_them():
    dev = tr.reduce_planes(planes())["devices"][0]
    assert tr.name_gaps(dev) == [
        ["after jit__prefill_impl before jit__decode_impl", pytest.approx(200e-9)],
        ["after jit__decode_impl before jit__decode_impl", pytest.approx(100e-9)],
    ]
    bd = tr.breakdown({"devices": [dev]})
    assert bd["device_ops"][0] == ["custom-call.4", pytest.approx(300e-9)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_text_proto_round_trip_through_the_profiler_reader(tmp_path):
    from jax.profiler import ProfileData

    text = cut_fixture.to_text_proto(planes(), keep_ns=700.0)
    path = tmp_path / "cut.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    back = tr.read_xplane(str(path))
    assert set(back) == {"/device:TPU:0"}
    ops = sorted(back["/device:TPU:0"]["XLA Ops"], key=lambda e: e[1])
    # cut after 700 ns from the plane's first event; times start at 0
    assert [(n, s, d) for n, s, d in ops] == [
        ("fusion.1", 0.0, 100.0), ("fusion.2", 50.0, 100.0),
        ("copy.3", 150.0, 50.0), ("fusion.1", 400.0, 100.0),
        ("custom-call.4", 600.0, 300.0),
    ]


def test_a_piece_of_a_real_v5e_trace():
    """The first 250 ms of device events of a traced run of
    qwen3-8b-int8.chat-open on one v5e chip (my chip run, PR 25), cut by
    ``cut_fixture.py cut``: seven decode steps of 41.9 ms, back to back."""
    red = tr.reduce_planes(tr.read_xplane(FIXTURE))
    assert len(red["devices"]) == 1
    dev = red["devices"][0]
    assert dev["plane"] == "/device:TPU:0"
    assert dev["op_events"] == 44681
    assert dev["window_s"] == pytest.approx(0.274339133)
    assert dev["busy_s"] == pytest.approx(0.274290403)
    assert dev["idle_pct"] == pytest.approx(0.017762686, rel=1e-4)
    decode = dev["modules"]["jit__decode_impl"]
    assert decode["count"] == 7 and decode["median_ns"] == pytest.approx(41887032.0)
    bd = tr.breakdown(red)
    # the two whole-cache copies lead; the layer loop that contains most
    # of the step is a container and is not listed
    assert bd["device_ops"][0][0] == "%copy.107 copy bf16[36,12,2048,8,128]"
    assert bd["device_ops"][0][1] == pytest.approx(0.033098526)
    assert not any(n.startswith("%while") for n, _ in bd["device_ops"])
    assert len(bd["device_ops"]) == 10 and 1 <= len(bd["idle_gaps"]) <= 10
    # what the decode roofline reader makes of it
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(HERE))
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(root, "perfbench", "layer_metrics",
                               "kernel.decode_hbm_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    with open(os.path.join(root, "perfbench", "configs", "qwen3-8b-int8",
                           "config.json")) as f:
        cfg = json.load(f)
    share = reader.read({
        "traces": [red], "flights": [], "max_slots": 12,
        "spec": {"quantization": "int8"}, "model_config": cfg,
        "peaks": {"hbm_bytes_per_s": 819e9},
    })
    # 7.568 GB of weights at 819 GB/s = 9.24 ms of a 41.89 ms step
    assert share == pytest.approx(100 * (7568097280 / 819e9) / 0.041887032)
    assert 22.0 < share < 22.1
