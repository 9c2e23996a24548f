"""A traced run reports every metric its cell declares: what each reader
of the device trace makes of a captured stretch, on hand-made reduced
traces; the capture taken again against a stubbed profile route; and
every ``device_trace`` metric of ``BENCHMARK.json`` against the traffic of
the cells that declare it."""

import asyncio
import importlib.util
import json
import os

import pytest

from perfbench import loadgen, run as bench, stretch

PB = os.path.dirname(os.path.abspath(loadgen.__file__))
ROOT = os.path.dirname(PB)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

FLASH = "kernel.flash_prefill_roofline"
PREFILL = "runner.prefill_device_ms_p50"
DECODE = "kernel.decode_hbm_roofline"
IDLE = "device.idle_pct"
MS = 1e6    # a trace counts nanoseconds


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(PB, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


READERS = [(n, reader(n)) for n in (FLASH, PREFILL, DECODE, IDLE)]
KERNEL_OP = "%flash_attention_prefill.7 = bf16[1,32,{t},128]{{3,2,1,0}} custom-call(%a)"


def whole(bucket):
    """A prefill program's milliseconds, here: 268 at 2048 tokens."""
    return 268.0 * bucket / 2048


def trace(*programs):
    """A reduced trace of one chip that ran these programs back to back,
    ``(name, ms)`` each, the first and the last cut by the trace's ends
    as in every trace of a chip under load. From the 1024 bucket up a
    prefill's 36 layers each call the kernel once (2.5 ms at 2048 tokens),
    and a program the trace cuts has as many calls as it had time for."""
    events, ops, at = [], {}, -0.0
    for name, ms in programs:
        events.append([name, at, ms * MS])
        at += ms * MS + 3000.0
        tokens = int(name.rsplit("_", 1)[1]) if name.startswith("jit_prefill_") else 0
        if tokens >= 1024:
            op = ops.setdefault(KERNEL_OP.format(t=tokens), {
                "count": 0, "total_ns": 0.0, "median_ns": 0.0})
            calls = int(36 * ms / whole(tokens))
            op["count"] += calls
            op["total_ns"] += calls * 2.5 * (tokens / 2048) ** 2 * MS
    window = at - 3000.0
    return {"devices": [{
        "window_s": window / 1e9, "busy_s": window / 1e9 - 1e-4,
        "idle_pct": 0.03, "module_events": events, "ops": ops,
    }]}


D, P1, P2 = ("jit__decode_impl", 41.9), ("jit_prefill_1024", whole(1024)), ("jit_prefill_2048", whole(2048))
# the 8B rag cell: buckets 1024 and 2048, 12 slots
with open(os.path.join(PB, "configs", "qwen3-8b-int8", "config.json")) as _f:
    CTX = {
        "buckets": [1024, 2048], "max_slots": 12, "flights": [],
        "model_config": json.load(_f), "spec": {"quantization": "int8"},
        "peaks": bench.peaks_for("TPU v5 lite"),
    }


def unread(reduced, **more):
    ctx = {**CTX, **more, "traces": [reduced]}
    return {n for n, mod in READERS if mod.read(ctx) is None}


@pytest.mark.parametrize("programs,nothing_for", [
    # a closed loop's 16 steps between two streams' ends
    ([D] * 16, {FLASH, PREFILL}),
    ([D] * 3 + [P2] + [D] * 12, set()),
    # the kernel ran, the mix's largest bucket did not
    ([D] * 3 + [P1] + [D] * 12, {PREFILL}),
    # the trace began inside a prefill: its layers' kernel calls are
    # whole operations, the program's event is not the program's time
    ([("jit_prefill_2048", 163.2)] + [D] * 16, {PREFILL}),
    ([D] * 15 + [("jit_prefill_2048", 10.9)], {PREFILL}),
    ([("jit_prefill_2048", 61.4)] + [D] * 4 + [P2] + [D] * 9, set()),
    # one whole program, but the only one: both ends cut it
    ([P2], {FLASH, PREFILL, DECODE}),
], ids=["decode-only", "prefill-2048", "prefill-1024", "cut-by-the-start",
        "cut-by-the-end", "one-cut-one-whole", "nothing-whole"])
def test_what_each_reader_makes_of_a_stretch(programs, nothing_for):
    got = trace(*programs)
    if programs == [P2]:
        got["devices"][0]["ops"] = {}
        got["devices"][0]["module_events"] = []
    assert unread(got) == nothing_for


def test_the_prefill_reads_as_the_whole_program_whatever_the_ends_cut():
    read = reader(PREFILL).read
    for programs in (
        [D] * 3 + [P2] + [D] * 12,
        [("jit_prefill_2048", 208.18)] + [D] * 4 + [P2] + [D] * 9 + [("jit_prefill_2048", 0.01)],
        [("jit_prefill_2048", 83.35)] + [D, P1, D, D, P2, D, P2, D],
    ):
        assert read({**CTX, "traces": [trace(*programs)]}) == pytest.approx(268.0)
    # a cell whose requests stop at 1024 reads that program
    only = trace(D, P1, D, D)
    assert read({**CTX, "buckets": [512, 1024], "traces": [only]}) == pytest.approx(whole(1024))
    # no buckets given: the largest the stretch ran whole
    assert read({"traces": [only]}) == pytest.approx(whole(1024))


def test_programs_cut_by_the_ends_of_a_trace_are_left_out():
    device = {"window_s": 1000e-9, "module_events": [
        ["jit_prefill_2048", -0.0, 300.0], ["jit__decode_impl", 300.0, 100.0],
        ["jit_prefill_2048", 400.0, 500.0], ["jit__decode_impl", 900.0, 100.0],
    ]}
    assert stretch.whole_programs(device) == device["module_events"][1:3]
    assert stretch.whole_programs({"window_s": 0.0, "module_events": []}) == []


def step(mode, ts):
    return {"mode": mode, "ts": ts}


def test_modes_are_a_letter_a_step():
    records = [step(m, 0.0) for m in (
        "decode", "decode", "prefill", "prefill_chunk", "spec_verify")] + [{}]
    assert stretch.modes(records) == "ddpcs?"


# ---- the capture, taken again ----------------------------------------------


class Chip:
    """``POST /v2/model-instances/{id}/profile`` answering from a list of
    stretches, each answer with a trace directory of its own, and what
    reducing that directory gives (in place of ``trace_reduce.py``)."""

    def __init__(self, tmp_path, stretches):
        self.tmp_path, self.stretches = tmp_path, list(stretches)
        self.calls, self.logged, self.reduced = [], [], []

    async def handle(self, request):
        from aiohttp import web

        n = len(self.calls)
        artifact = self.tmp_path / f"profile-{n}"
        artifact.mkdir()
        (artifact / "t.xplane.pb").write_bytes(b"x")
        self.calls.append(dict(request.query))
        records = [
            step("prefill" if name.startswith("jit_prefill") else "decode", float(i))
            for i, (name, _ms) in enumerate(self.stretches[n])
        ]
        return web.json_response({
            "requested": int(request.query["steps"]),
            "steps_captured": len(records), "profiler": "jax",
            "artifact": str(artifact), "error": "", "records": records,
        })

    def reduce_trace(self, artifact, out_path):
        n = int(artifact.rsplit("-", 1)[1])
        self.reduced.append(n)
        return trace(*self.stretches[n])

    def capture(self, readers=READERS, keep=False):
        async def go():
            from aiohttp import web

            app = web.Application()
            app.router.add_post("/v2/model-instances/{id}/profile", self.handle)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            self.ctx = dict(CTX)
            try:
                return await bench.capture_served(
                    f"http://127.0.0.1:{port}", {}, [{"id": 7}], 16, 5.0,
                    readers, self.ctx, str(self.tmp_path), keep,
                )
            finally:
                await runner.cleanup()

        saved = bench.log, bench.reduce_trace
        bench.log, bench.reduce_trace = self.logged.append, self.reduce_trace
        try:
            return asyncio.run(go())
        finally:
            bench.log, bench.reduce_trace = saved

    def retakes(self):
        return [l for l in self.logged if l["phase"] == "capture_retaken"]


def test_a_capture_without_a_prefill_is_taken_again(tmp_path):
    chip = Chip(tmp_path, [[D] * 16, [D] * 3 + [P2] + [D] * 12])
    profiles = chip.capture()
    assert len(chip.calls) == 2 and chip.calls[0]["steps"] == "16"
    assert [p["_capture"] for p in profiles] == [2]
    assert profiles[0]["artifact"] == str(tmp_path / "profile-1")
    # both traces were reduced and deleted; the second is the run's
    assert chip.reduced == [0, 1]
    assert not (tmp_path / "profile-0").exists() and not (tmp_path / "profile-1").exists()
    assert chip.retakes() == [{
        "phase": "capture_retaken", "capture": 1, "modes": ["d" * 16],
        "unread": [FLASH, PREFILL], "errors": [],
    }]
    # and every reader of the cell reads its number from it: the line is whole
    assert unread(chip.ctx["traces"][0]) == set()
    assert reader(PREFILL).read(chip.ctx) == pytest.approx(268.0)
    assert 5.0 < reader(FLASH).read(chip.ctx) < 10.0


def test_a_capture_that_serves_is_the_only_one(tmp_path):
    chip = Chip(tmp_path, [[D, P2] + [D] * 14])
    profiles = chip.capture(keep=True)
    assert len(chip.calls) == 1 and chip.retakes() == []
    assert profiles[0]["_capture"] == 1
    # --keep-trace
    assert (tmp_path / "profile-0" / "t.xplane.pb").exists()


def test_a_cell_without_a_reader_of_the_prefill_never_retakes(tmp_path):
    # the chat cell: the decode program's roofline and the idle share
    chip = Chip(tmp_path, [[D] * 16])
    chip.capture([r for r in READERS if r[0] in (DECODE, IDLE)])
    assert len(chip.calls) == 1 and chip.retakes() == []
    # and a CPU rehearsal asks no reader at all
    chip = Chip(tmp_path / "cpu", [[]])
    (tmp_path / "cpu").mkdir()
    chip.capture([])
    assert len(chip.calls) == 1 and chip.retakes() == []


def test_barren_captures_fail_in_the_runs_own_words(tmp_path):
    cut = [D] * 15 + [("jit_prefill_2048", 10.9)]
    chip = Chip(tmp_path, [[D] * 16] * (bench.MAX_CAPTURES - 2) + [[D, P1] + [D] * 14, cut])
    with pytest.raises(bench.BenchFailure) as failure:
        chip.capture()
    assert len(chip.calls) == bench.MAX_CAPTURES
    said = str(failure.value)
    # the last capture served the kernel's reader and not the program's
    assert PREFILL in said and FLASH not in said and "dddddddddddddddp" in said
    assert f"{bench.MAX_CAPTURES} captures of 16 steps" in said
    # every retake but the last, which is the failure, is in the log
    assert [l["capture"] for l in chip.retakes()] == list(range(1, bench.MAX_CAPTURES))
    assert chip.retakes()[-1]["unread"] == [PREFILL]
    assert chip.retakes()[0]["unread"] == [FLASH, PREFILL]
    assert not any((tmp_path / f"profile-{i}").exists() for i in range(bench.MAX_CAPTURES))


def test_an_engine_that_cuts_prompts_otherwise_than_the_plan_fails_at_the_first_capture(tmp_path):
    # the plan reaches 1024 and 2048; the engine ran a 4096 program
    chip = Chip(tmp_path, [[D] * 3 + [("jit_prefill_4096", 600.0)] + [D] * 12, [D, P2, D]])
    with pytest.raises(bench.BenchFailure, match=r"\[4096\].*reach \[1024, 2048\]"):
        chip.capture()
    assert len(chip.calls) == 1 and chip.retakes() == []


def test_a_capture_whose_profiler_failed_is_taken_again(tmp_path):
    chip = Chip(tmp_path, [[D] * 16, [D, P2] + [D] * 14])
    reduce_trace = chip.reduce_trace
    chip.reduce_trace = lambda a, o: None if a.endswith("-0") else reduce_trace(a, o)
    profiles = chip.capture()
    assert [p["_capture"] for p in profiles] == [2]
    assert set(chip.retakes()[0]["unread"]) == {FLASH, PREFILL, DECODE, IDLE}


# ---- BENCHMARK.json against the cells' traffic ------------------------------


def buckets_of(cell):
    """The prefill buckets the cell's requests reach, as a run draws them."""
    mix = loadgen.load_traffic(cell["traffic"], PB)
    config = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, os.path.dirname(config["file"]), "deployment.json")) as f:
        max_seq = int(json.load(f)["model"]["max_seq_len"])
    if mix["loop"] == "open":
        with open(os.path.join(PB, "cells", cell["name"] + ".json")) as f:
            rate = json.load(f)["rate_rps"]
        planned = loadgen.plan_open(mix, rate, BENCH["run_seconds"], 3000000001)
    else:
        planned = loadgen.plan_requests(mix, int(mix["pool"]), 3000000001)
    return mix, loadgen.buckets_of(planned, max_seq)


TRACED = [
    (cell["name"], m["name"]) for cell in BENCH["workloads"]
    for m in bench.metrics_of(BENCH, "per_layer", cell["name"])
    if m["source"] == "device_trace"
]


@pytest.mark.parametrize("cell_name,metric", TRACED)
def test_the_cells_traffic_can_serve_every_device_trace_metric_it_declares(cell_name, metric):
    """A stretch that holds a whole prefill at each bucket the cell's
    requests reach, decode steps between: if not even this serves the
    reader, no capture of the cell will, and every traced run of it
    fails after ``MAX_CAPTURES`` (a mix whose prompts never reach a flash
    bucket must not declare the flash roofline)."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == cell_name)
    mix, buckets = buckets_of(cell)
    programs = [D]
    for b in buckets:
        programs += [(f"jit_prefill_{b}", whole(b)), D, D]
    ctx = {**CTX, "buckets": buckets, "traces": [trace(*programs)]}
    assert bench.load_reader(metric).read(ctx) is not None
    assert int(mix["trace_steps"]) == 16


def test_a_mix_that_never_reaches_the_kernel_cannot_declare_its_roofline():
    short = trace(D, ("jit_prefill_64", whole(64)), D, ("jit_prefill_512", whole(512)), D)
    assert unread(short, buckets=[64, 512]) == {FLASH}
