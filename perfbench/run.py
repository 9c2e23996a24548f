#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, through the user's entry points.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>
    python perfbench/run.py --workload <cell> --rehearse        # CPU, counts only

Starts ``python -m gpustack_tpu start`` (server + embedded worker, the
real detector) as a child, logs in, deploys the cell's configuration with
``POST /v2/models`` (``local_path`` = the configuration's directory: a
``config.json`` and no weights gets seeded random weights and the byte
tokenizer), waits for ``running``, warms the prefill buckets the cell's
requests reach, drives the cell's traffic at the **server's** port for
``--seconds``, checks the answers, stops every process it started, and
prints one JSON object as its last line.

``--trace 0`` reports the cell's end-to-end metrics, taken at the client.
``--trace 1`` runs the same window with a profiler capture inside it and
reports the per-layer metrics (each by a reader of its own in
``perfbench/layer_metrics/``) and the device's busy time.
``--trace 2`` is a ``--trace 0`` run followed by a short traced tail, in
one process: up to the close of the measured window it does what
``--trace 0`` does, and takes every end-to-end number from that window.
Then the same traffic goes on, the profiler is started and stopped once
for nothing, a capture of the mix's ``trace_steps`` is made, and the
last line holds the end-to-end *and* the per-layer metrics. Its readers
get the window's counters and spans and the tail's device trace. That
trace is reduced while the traffic goes on, and if a ``device_trace``
reader of the cell reads nothing from it (a closed loop's 16 steps now
and then hold no whole prefill), it is deleted and the capture made
again, at most ``MAX_CAPTURES`` times; a run that still has nothing to
read fails and says for which metric, rather than print a line without
a metric its cell declares.

This process never imports JAX: the engine child needs the chip. The
device in the last line is what the engine's ``/healthz`` names, and a
run whose engine is not on ``tpu`` fails (``--rehearse`` excepted, which
reports no metric at all).
"""

from __future__ import annotations

T_PROCESS_START = __import__("time").time()

import argparse
import asyncio
import concurrent.futures
import contextlib
import importlib.util
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench import checks, cluster as cl, loadgen, stretch  # noqa: E402
from perfbench.cluster import BenchFailure  # noqa: E402

RUN_DEADLINE_S = 1150.0     # a first, compiling run may take 1200 s
TAIL_LEAD_S = 1.5           # --trace 2: untraced traffic before the captures
TAIL_BUDGET_S = 30.0        # and how long one capture may wait for its steps
# --trace 2 captures until the cell's device_trace readers are served. One
# capture in four is not (24 captures, PERF.md section 3): eight all fail
# once in 65,000 runs, and cost 13 s each of the 360 s a run may take.
MAX_CAPTURES = 8


def log(record: Dict[str, Any]) -> None:
    """Progress lines; the result is the *last* line of stdout."""
    print(json.dumps(record), flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def find(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchFailure(f"BENCHMARK.json has no {what} named {name!r}")


def metrics_of(bench: Dict[str, Any], group: str, cell: str) -> List[Dict[str, Any]]:
    """The metrics of ``group`` that this cell reports."""
    return [
        m for m in bench[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )


def cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir()) if not n.startswith("."))
    except OSError:
        return 0


class Setup:
    """What one cell needs, read from the files its names point at."""

    def __init__(self, args: argparse.Namespace):
        self.bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        self.cell = find(self.bench["workloads"], args.workload, "workload")
        self.rehearse = bool(args.rehearse)
        self.mix = loadgen.load_traffic(self.cell["traffic"], HERE)
        cell_path = os.path.join(HERE, "cells", f"{self.cell['name']}.json")
        self.cell_params = (
            load_json(cell_path) if os.path.exists(cell_path) else {}
        )
        config = find(self.bench["configs"], self.cell["config"], "config")
        real_dir = os.path.dirname(os.path.join(ROOT, config["file"]))
        if self.rehearse:
            self.config_dir = os.path.join(HERE, "rehearsal", "tiny-qwen3")
            self.deployment = load_json(
                os.path.join(HERE, "rehearsal", "deployment.json")
            )
            self.platform = self.deployment["platform"]
            self.server_args = [
                os.path.join(ROOT, a) if a.startswith("perfbench/") else a
                for a in self.deployment["server_args"]
            ]
            self.scale = float(self.deployment["length_scale"])
            self.cell_params = {"rate_rps": self.deployment["rate_rps"]}
            # a rehearsal runs beside other clusters on one machine (the
            # test suite's), whose workers all probe the same default band
            # of engine ports and can pick the same one: take another band
            os.environ.setdefault(
                "GPUSTACK_TPU_ENGINE_PORT_BASE",
                str(43000 + (os.getpid() % 100) * 200),
            )
            # as many (tiny) replicas as the cell's own deployment has,
            # so that a fleet cell's rehearsal goes through the fleet path
            self.replicas = int(
                load_json(os.path.join(real_dir, "deployment.json"))
                ["model"].get("replicas", 1)
            )
        else:
            self.config_dir = real_dir
            self.deployment = load_json(
                os.path.join(self.config_dir, "deployment.json")
            )
            self.platform = "tpu"
            self.server_args = []
            self.scale = 1.0
            self.replicas = int(self.deployment["model"].get("replicas", 1))
            if self.replicas > self.cell["chips"]:
                raise BenchFailure("more replicas than the cell has chips")
        self.model_config = load_json(
            os.path.join(self.config_dir, "config.json")
        )
        self.spec = {
            **self.deployment["model"],
            "name": self.deployment["name"],
            "local_path": self.config_dir,
            "replicas": self.replicas,
        }
        self.seconds = float(args.seconds)
        self.seed = int(args.seed)
        self.trace = int(args.trace)     # 0, 1 (in the window) or 2 (after it)

    def plan(self) -> List[loadgen.Planned]:
        if self.mix["loop"] == "open":
            rate = self.cell_params.get("rate_rps")
            if not rate:
                raise BenchFailure(
                    f"open-loop cell {self.cell['name']} has no rate_rps in "
                    f"perfbench/cells/{self.cell['name']}.json"
                )
            return loadgen.plan_open(
                self.mix, float(rate), self.seconds, self.seed, self.scale
            )
        return loadgen.plan_requests(
            self.mix, int(self.mix["pool"]), self.seed, self.scale
        )


def warm_up(
    setup: Setup, base: str, hdrs: Dict[str, str], engines, planned
) -> List[int]:
    """One short streamed request for each prefill bucket the window's
    requests reach, longest prompt of the bucket, sent to every replica
    through its worker's proxy (the server's proxy would pick one): the
    window then meets no shape for the first time."""
    max_seq = int(setup.spec["max_seq_len"])
    buckets = loadgen.buckets_of(planned, max_seq)
    template = int(setup.mix.get("template_tokens", 0))
    longest = {}
    for p in planned:
        b = loadgen.buckets_of([p], max_seq)[0]
        longest[b] = max(longest.get(b, 0), p.prompt_tokens)
    rng = random.Random(setup.seed)
    for b in buckets:
        p = loadgen.Planned(
            index=-1, prompt_tokens=longest[b], output_tokens=4,
            text=loadgen.seeded_text(rng, longest[b] - template),
            sample_seed=1,
        )
        body = loadgen.chat_body(setup.spec["name"], p, 1.0)
        t0 = time.time()
        # the replicas at once, one thread each
        with concurrent.futures.ThreadPoolExecutor(len(engines)) as pool:
            answers = list(pool.map(
                lambda e: checks.stream_once(e[0], e[1], body), engines
            ))
        for got in answers:
            if got["chunks"] != 4 or got["prompt_tokens"] != longest[b]:
                raise BenchFailure(
                    f"warm-up of bucket {b}: {got}, wanted 4 chunks and "
                    f"{longest[b]} prompt tokens"
                )
        log({"phase": "warm_up", "bucket": b, "prompt_tokens": longest[b],
             "seconds": round(time.time() - t0, 3)})
    # once through the server's own port, so its proxy has dialled too
    p = loadgen.Planned(
        index=-1, prompt_tokens=longest[buckets[0]], output_tokens=2,
        text=loadgen.seeded_text(rng, longest[buckets[0]] - template),
        sample_seed=1,
    )
    checks.stream_once(base, hdrs, loadgen.chat_body(setup.spec["name"], p, 1.0))
    return buckets


@contextlib.contextmanager
def serving(setup: Setup, run_dir: str, planned):
    """For the tools that start a cell once and ask it many things
    (``sweep.py``, ``check_noise.py``): the cell's cluster up, its
    configuration deployed on the device the cell asks for, the buckets of
    ``planned`` warm. Yields ``(cluster, hdrs, insts, workers, engines,
    buckets)``; stops everything on the way out, whatever happened."""
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    cluster = cl.Cluster(run_dir, setup.server_args)
    try:
        cluster.start()
        deadline = time.time() + 1100
        hdrs = cluster.wait_ready(setup.cell["chips"], deadline)
        model, insts = cl.deploy(cluster.base, hdrs, setup.spec, deadline)
        workers = cl.worker_endpoints(cluster.data_dir)
        for i in insts:
            cl.check_device(cl.engine_health(workers, i), setup.platform, 1)
        cluster.note_engines()
        engines = [cl.engine_url(workers, i) for i in insts]
        buckets = warm_up(setup, cluster.base, hdrs, engines, planned)
        yield cluster, hdrs, insts, workers, engines, buckets
        cluster.stop()
    except BaseException:
        cluster.dump_logs()
        raise
    finally:
        cluster.kill()


async def capture_profiles(
    base: str, hdrs: Dict[str, str], insts, steps: int, budget: float,
) -> List[Dict[str, Any]]:
    """``POST /v2/model-instances/{id}/profile`` on every replica at once;
    each wraps the next ``steps`` busy scheduler steps in ``jax.profiler``
    and answers when they have run and the trace is written, or after
    ``budget`` seconds without them."""
    import aiohttp

    async def one(session, inst):
        url = (
            f"{base}/v2/model-instances/{inst['id']}/profile"
            f"?steps={steps}&timeout_s={budget}"
        )
        t0 = time.time()
        try:
            async with session.post(
                url, headers=hdrs,
                timeout=aiohttp.ClientTimeout(total=budget + 100),
            ) as r:
                body = await r.json()
                body["_status"] = r.status
        except Exception as e:  # reported; the run then has no trace
            body = {"error": f"{type(e).__name__}: {e}"}
        body["_instance"] = inst["id"]
        body["_t0_wall"] = t0
        body["_t1_wall"] = time.time()
        return body

    async with aiohttp.ClientSession() as session:
        return list(await asyncio.gather(*(one(session, i) for i in insts)))


def reduce_trace(artifact: str, out_path: str) -> Optional[Dict[str, Any]]:
    """The xplane reduction, in a child that may import JAX (on the CPU,
    so that it can run while the engine holds the chip): this process
    never touches JAX."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_SKIP_MDS_QUERY="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"),
         artifact, out_path],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        log({"phase": "trace_reduce", "error": proc.stderr[-1500:]})
        return None
    return load_json(out_path)


def reduce_profiles(
    profiles: List[Dict[str, Any]], run_dir: str, keep: bool
) -> List[Dict[str, Any]]:
    """Each capture's trace reduced (``trace-<instance>.json`` in the run
    directory) and, unless it is to be kept, deleted."""
    reduced = []
    for p in profiles:
        log({"phase": "profile", **profile_summary(p)})
        if p.get("profiler") == "jax" and p.get("artifact"):
            got = reduce_trace(
                p["artifact"],
                os.path.join(run_dir, f"trace-{p['_instance']}.json"),
            )
            if got is not None:
                reduced.append(got)
            if not keep:
                shutil.rmtree(p["artifact"], ignore_errors=True)
    return reduced


async def capture_served(
    base: str, hdrs: Dict[str, str], insts, steps: int, budget: float,
    readers, ctx: Dict[str, Any], run_dir: str, keep: bool = False,
) -> List[Dict[str, Any]]:
    """``capture_profiles`` until the trace serves ``readers`` (the
    cell's ``device_trace`` metrics, each with its reader's module): the
    capture is reduced into ``ctx["traces"]`` while the traffic goes on
    and each reader asked for its number. The first capture that gives
    every one is the run's, as when one capture was all a run made. One
    that does not is logged with what its steps were, and after
    ``MAX_CAPTURES`` of them the run fails in its own words."""
    loop = asyncio.get_running_loop()
    for taken in range(1, MAX_CAPTURES + 1):
        profiles = await capture_profiles(base, hdrs, insts, steps, budget)
        ctx["traces"] = await loop.run_in_executor(
            None, reduce_profiles, profiles, run_dir, keep
        )
        unread = [name for name, mod in readers if mod.read(ctx) is None]
        if not unread:
            for p in profiles:
                p["_capture"] = taken
            return profiles
        seen = {
            "modes": [stretch.modes(p.get("records") or []) for p in profiles],
            "errors": [p["error"] for p in profiles if p.get("error")],
        }
        if taken < MAX_CAPTURES:
            log({"phase": "capture_retaken", "capture": taken,
                 "unread": unread, **seen})
    raise BenchFailure(
        f"{MAX_CAPTURES} captures of {steps} steps held nothing for "
        f"{', '.join(unread)} to read; the last one: {json.dumps(seen)} "
        f"(a letter a step: p prefill, c prefill chunk, d decode, s "
        f"speculative verify)"
    )


def reader_path(metric: str) -> Optional[str]:
    """``layer_metrics/<metric>.py``; a quantity split over cells that
    report different end-to-end metrics (``<metric>.<part>``, one entry
    for each part) shares the reader ``layer_metrics/<metric>.py``."""
    for name in (metric, metric.rsplit(".", 1)[0]):
        path = os.path.join(HERE, "layer_metrics", f"{name}.py")
        if os.path.exists(path):
            return path
    return None


def load_reader(metric: str):
    path = reader_path(metric)
    if path is None:
        raise BenchFailure(f"per-layer metric {metric} has no reader")
    spec = importlib.util.spec_from_file_location(
        "perfbench_reader_" + metric.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_layer_metrics(
    setup: Setup, ctx: Dict[str, Any]
) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of this cell through its own reader,
    ``perfbench/layer_metrics/<name>.py:read(ctx)``. A reader that finds
    nothing to read returns None; its metric is left out of the line (a
    program from before a counter existed still gets its line), and the
    log says so."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in metrics_of(setup.bench, "per_layer", setup.cell["name"]):
        value = load_reader(m["name"]).read(ctx)
        if value is None:
            log({"phase": "metric_left_out", "name": m["name"]})
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def client_numbers(red: Dict[str, Any]) -> Dict[str, float]:
    """Every client-side number of the window, whichever of them the cell
    reports end to end: the run's log carries all, so that a metric's
    place (end to end or per layer) can be judged from runs already made."""
    values = {"output_tok_s": red["tokens"] / red["seconds"]}
    if red["ttft_ms"]:
        values["ttft_ms_p50"] = loadgen.percentile(red["ttft_ms"], 0.50)
        values["ttft_ms_p90"] = loadgen.percentile(red["ttft_ms"], 0.90)
    if red["gaps_ms"]:
        values["itl_ms_p99"] = loadgen.percentile(red["gaps_ms"], 0.99)
    return values


def engine_stalls(flights: List[List[Dict[str, Any]]]) -> Dict[str, float]:
    """In each engine's step records the longest scheduler step and the
    longest time from one step's record to the next."""
    if not any(flights):
        return {}
    return {
        "engine_step_ms_max": round(
            max(r["dur_ms"] for engine in flights for r in engine), 3),
        "engine_step_to_step_ms_max": round(max(
            ((b["ts"] - a["ts"]) * 1e3
             for engine in flights for a, b in zip(engine, engine[1:])),
            default=0.0,
        ), 3),
    }


def stalls(
    window: loadgen.Window, flights: List[List[Dict[str, Any]]],
    seconds: float,
) -> Dict[str, float]:
    """Where a window lost time, if it did (2 of 45 closed-loop runs of
    PR 25 lost 4 % and 13 % of their tokens without an error): the longest
    silence over all streams at the client, and in each engine the longest
    scheduler step and the longest time from one step's record to the
    next. A silence with no long step beside it was lost outside the
    engine's loop."""
    t_end = window.t0 + seconds
    times = sorted(
        t for r in window.results for t in r.chunk_times if t <= t_end
    )
    silence = max(
        ((b - a) * 1e3 for a, b in zip(times, times[1:])), default=0.0
    )
    return {
        "client_silence_ms_max": round(silence, 3), **engine_stalls(flights)
    }


def end_to_end(setup: Setup, red: Dict[str, Any], setup_s: float) -> Dict[str, Any]:
    values = {"setup_s": setup_s, **client_numbers(red)}
    out = {}
    for m in metrics_of(setup.bench, "end_to_end", setup.cell["name"]):
        if m["name"] not in values:
            raise BenchFailure(
                f"nothing measured for {m['name']}: {red['attempted']} "
                f"requests, {len(red['ttft_ms'])} first tokens"
            )
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def run(args: argparse.Namespace, cluster_box: List[cl.Cluster]) -> Dict[str, Any]:
    setup = Setup(args)
    deadline = T_PROCESS_START + RUN_DEADLINE_S
    run_dir = os.path.join(
        ROOT, "chiprun_out", "perfbench", "runs",
        f"{setup.cell['name']}-s{setup.seed}-t{setup.trace}"
        + ("-rehearse" if setup.rehearse else ""),
    )
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    planned = setup.plan()
    log({"phase": "plan", "cell": setup.cell["name"], "seed": setup.seed,
         "loop": setup.mix["loop"], "rate_rps": setup.cell_params.get("rate_rps"),
         "drawn": loadgen.describe(planned)})

    cluster = cl.Cluster(run_dir, setup.server_args)
    cluster_box.append(cluster)
    cluster.start()
    hdrs = cluster.wait_ready(setup.cell["chips"], deadline)
    log({"phase": "server_ready", "at_s": round(time.time() - T_PROCESS_START, 3)})
    model, insts = cl.deploy(cluster.base, hdrs, setup.spec, deadline)
    workers = cl.worker_endpoints(cluster.data_dir)
    healths = [cl.engine_health(workers, i) for i in insts]
    devs = [cl.check_device(h, setup.platform, 1) for h in healths]
    cluster.note_engines()
    log({"phase": "running", "at_s": round(time.time() - T_PROCESS_START, 3),
         "instances": [i["id"] for i in insts], "device": devs[0]})
    engines = [cl.engine_url(workers, i) for i in insts]
    buckets = warm_up(setup, cluster.base, hdrs, engines, planned)
    # what the readers get: filled as the run goes on
    ctx: Dict[str, Any] = {
        "model_config": setup.model_config, "spec": setup.spec,
        "buckets": buckets,
        "max_seq_len": int(setup.spec["max_seq_len"]),
        "max_slots": int(setup.spec["max_slots"]),
    }
    if setup.trace:
        ctx["peaks"] = (
            {} if setup.rehearse else peaks_for(devs[0]["device_kind"])
        )

    def flights_between(t_lo: float, t_hi: float):
        """Per engine, its step records sealed in ``(t_lo, t_hi]``."""
        return [
            [r for r in cl.engine_get(
                workers, inst, "/debug/flight?limit=2048"
            )["records"] if t_lo < r["ts"] <= t_hi]
            for inst in insts
        ]

    def window_records(window: loadgen.Window):
        """The engines' step records and the server's hop traces of the
        measured window: every run reads the former for its log, a traced
        run's readers take their numbers from both."""
        t_lo, t_hi = window.t0_wall, window.t0_wall + window.seconds
        flights = flights_between(t_lo, t_hi)
        if not setup.trace:
            return flights, []
        hops = cl.expect(
            cl.http(
                "GET",
                f"{cluster.base}/v2/debug/traces?component=server"
                f"&model={setup.spec['name']}&phase=connect&limit=200",
                headers=hdrs,
            ), 200, "hop traces",
        )["items"]
        return flights, [
            h for h in hops if t_lo <= h.get("started_at", 0) <= t_hi
        ]

    cache_before = cache_entries()
    during = tail = None
    profiles: List[Dict[str, Any]] = []
    steps = int(setup.mix.get("trace_steps", 16))
    # --trace 1 captures late in the window and reads its counters and
    # client-side numbers from the part of the window before the capture
    # (PR 25: stopping the profiler held the engine still for seconds)
    trace_at_s = float(setup.mix.get("trace_at", 0.8)) * setup.seconds
    if setup.trace == 1:
        async def during(window):
            await asyncio.sleep(
                max(0.0, window.t0 + trace_at_s - time.perf_counter())
            )
            profiles.extend(await capture_profiles(
                cluster.base, hdrs, insts, steps,
                max(5.0, window.seconds - trace_at_s - 2.0),
            ))
    read_at_close = None
    if setup.trace == 2:
        # the readers of the device trace decide whether a capture serves;
        # a CPU run's trace has no chip's plane, and its one capture stands
        traced = [] if setup.rehearse else [
            (m["name"], load_reader(m["name"]))
            for m in metrics_of(setup.bench, "per_layer", setup.cell["name"])
            if m["source"] == "device_trace"
        ]

        # --trace 2: the window is measured untraced and closed; the
        # traffic goes on and only then is anything traced. The window's
        # records are read first: the engines keep stepping through the
        # tail and their rings hold 2048 steps.
        async def tail(window):
            nonlocal read_at_close
            read_at_close = await asyncio.get_running_loop().run_in_executor(
                None, window_records, window
            )
            ctx.update(flights=read_at_close[0], hops=read_at_close[1])
            await asyncio.sleep(TAIL_LEAD_S)
            # the profiler's first start in a process costs more than a
            # later one: start and stop it once and throw that trace away
            for p in await capture_profiles(
                cluster.base, hdrs, insts, 1, TAIL_BUDGET_S
            ):
                log({"phase": "profiler_first_start", **profile_summary(p)})
                shutil.rmtree(p.get("artifact") or "", ignore_errors=True)
            profiles.extend(await capture_served(
                cluster.base, hdrs, insts, steps, TAIL_BUDGET_S,
                traced, ctx, run_dir, args.keep_trace,
            ))
    setup_s = time.time() - T_PROCESS_START
    window = asyncio.run(loadgen.drive(
        cluster.base, hdrs, setup.spec["name"], setup.mix, planned,
        setup.seconds, during, tail,
    ))
    cache_after = cache_entries()
    red = loadgen.reduce_window(
        window, setup.mix, trace_at_s if setup.trace == 1 else None
    )
    # before anything else pushes the window's steps out of the rings
    flights, hops = read_at_close or window_records(window)
    t_cut = window.t0_wall + red["seconds"]
    flights = [[r for r in e if r["ts"] <= t_cut] for e in flights]
    hops = [h for h in hops if h.get("started_at", 0) <= t_cut]
    log({"phase": "window", "setup_s": round(setup_s, 3),
         "compiled_in_window": cache_after - cache_before,
         "cache_entries": cache_after, "buckets": buckets,
         **{k: v for k, v in red.items() if k not in ("ttft_ms", "gaps_ms")},
         "ttft_samples": len(red["ttft_ms"]), "gap_samples": len(red["gaps_ms"]),
         # a CPU run's times are no one's numbers: counts only
         **({} if setup.rehearse else {
             "client": client_numbers(red),
             "stalls": stalls(window, flights, red["seconds"]),
         })})
    if setup.trace == 2 and not setup.rehearse:
        # what the captures cost the engine: its steps from the window's
        # close to now (a stop that held the scheduler shows as the gap
        # to the first record after it, which is sealed after the answer)
        t_lo, t_hi = window.t0_wall + window.seconds, time.time()
        after = flights_between(t_lo, t_hi)
        log({"phase": "tail", "seconds": round(t_hi - t_lo, 3),
             "steps": sum(map(len, after)),
             "captures": max(p["_capture"] for p in profiles),
             "capture_s": [
                 round(p["_t1_wall"] - p["_t0_wall"], 3) for p in profiles
             ],
             "stalls": engine_stalls(after)})

    if setup.trace:
        ctx.update(flights=flights, hops=hops)

    cl.poll(
        "the engines to drain", time.time() + 60,
        lambda: all(
            cl.engine_health(workers, i)["slots_used"] == 0 for i in insts
        ),
    )
    verdict = checks.run_checks(setup, cluster.base, hdrs, engines, buckets)
    log({"phase": "checks", **verdict})
    healths = [cl.engine_health(workers, i) for i in insts]
    for h in healths:
        cl.check_device(h, setup.platform, 1)
    if not sum(h.get("tokens_generated", 0) for h in healths):
        raise BenchFailure("the engines generated no tokens")
    peak = max(cl.peak_memory_bytes(h) for h in healths)
    log({"phase": "engines", "health": [
        {k: h.get(k) for k in (
            "steps", "flight_overhead_ratio", "programs_traced_total",
            "programs_compiled_total", "compile_seconds_total",
        )} for h in healths
    ]})
    cluster.stop()
    log({"phase": "stopped", "at_s": round(time.time() - T_PROCESS_START, 3)})

    correct = (
        verdict["ok"] and red["mismatched"] == 0
        and cache_after == cache_before
    )
    device = {
        "platform": devs[0]["platform"], "kind": devs[0]["device_kind"],
        "count": len(devs), "memory_peak_bytes": peak,
    }
    result: Dict[str, Any] = {
        "correct": correct, "attempted": red["attempted"],
        "failed": red["failed"], "metrics": {}, "device": device,
        "workload": setup.cell["name"], "seed": setup.seed,
        "seconds": setup.seconds, "completed": red["completed"],
        "compiled_in_window": cache_after - cache_before,
    }
    if setup.rehearse:
        # counts only: a CPU run never carries a device metric's name
        result["rehearsal"] = True
        result["counts"] = {
            "tokens": red["tokens"], "ttft_samples": len(red["ttft_ms"]),
            "gap_samples": len(red["gaps_ms"]), "buckets": buckets,
        }
        if setup.trace:
            result["counts"]["flight_records"] = sum(map(len, ctx["flights"]))
            result["counts"]["hops"] = len(ctx["hops"])
            result["counts"]["profiles"] = [
                p.get("steps_captured") for p in profiles
            ]
    elif setup.trace != 1:
        # from the untraced window (--trace 2: closed before any tracing)
        result["metrics"] = end_to_end(setup, red, setup_s)
    if not setup.trace:
        return result

    if setup.trace == 1:
        ctx["traces"] = reduce_profiles(profiles, run_dir, args.keep_trace)
    devices = [d for r in ctx["traces"] for d in r["devices"]]
    if devices:
        from perfbench import trace_reduce

        device["busy_s"] = sum(d["busy_s"] for d in devices) / len(devices)
        device["window_s"] = sum(d["window_s"] for d in devices) / len(devices)
        result["breakdown"] = trace_reduce.breakdown({"devices": devices})
    elif not setup.rehearse:
        raise BenchFailure(
            "the traced run has no device plane with operations: "
            + json.dumps([p.get("error") for p in profiles])
        )
    ctx.update(loadgen=red, healths=healths, peak_memory_bytes=peak)
    layer = read_layer_metrics(setup, ctx)
    if setup.rehearse:
        # the readers ran over a CPU run's records and its trace, which
        # has no chip's plane: which of them found something, no number
        log({"phase": "metrics_read", "names": sorted(layer)})
    else:
        result["metrics"].update(layer)
    return result


def profile_summary(p: Dict[str, Any]) -> Dict[str, Any]:
    return {
        k: p.get(k) for k in (
            "_instance", "_status", "steps_captured", "profiler",
            "artifact", "error",
        )
    }


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise BenchFailure(
            f"device kind {kind!r} is not in perfbench/peaks.json"
        )
    return table[kind]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="the same path on the CPU with a tiny model: counts only",
    )
    ap.add_argument(
        "--keep-trace", action="store_true",
        help="leave the profiler's files in the run directory",
    )
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = 4.0 if args.rehearse else float(
            load_json(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
        )

    def on_alarm(signum, frame):
        raise BenchFailure(f"run deadline of {RUN_DEADLINE_S:.0f}s passed")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(int(RUN_DEADLINE_S))
    box: List[cl.Cluster] = []
    try:
        result = run(args, box)
    except BaseException as e:
        for c in box:
            c.dump_logs()
        sys.stderr.write(f"perfbench: {type(e).__name__}: {e}\n")
        return 1
    finally:
        signal.alarm(0)
        for c in box:
            c.kill()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
