"""bench.py's pure parts and its refusal to measure without a chip: the
schedules are seeded and pure, a CPU smoke prints counts only, and
without a TPU in its own process bench.py exits non-zero and prints no
rate (it never swaps in another model or platform).
"""

import json
import os
import subprocess
import sys

import bench

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def test_bench_refuses_to_run_without_a_tpu(tmp_path):
    """JAX on the CPU (as everywhere off the chip): exit code 3, one
    compact error line, no rate, no round file."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_ROUND="0")
    env.pop("BENCH_SMOKE", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["metric"] == "error" and last["value"] == 0
    assert "tok" not in proc.stdout.split("BENCH_SMOKE")[0]
    assert "measures on a TPU" in last["detail"]["error"]


def test_counts_only_drops_every_time_and_rate():
    detail = {
        "requests": 6, "output_tokens": 96, "wall_s": 1.5,
        "total_tok_per_s": 300.0, "p50_ttft_ms": 12.0, "mfu_est": None,
        "phases": {"ttft": {"p50_ms": 1.0}},
        "flight": {
            "steps": 40, "tokens_per_step": 2.4, "padding_waste_pct": 20.0,
            "queue_wait_ms_p50": 3.0, "recorder_overhead_ratio": 0.0003,
            "modes": {"decode": {"step_ms_p50": 1.0}},
        },
        "multiturn": {
            "token_parity": True, "turns_hit": 8, "cold_ttft_ms_p50": 9.0,
            "ttft_improvement": 0.5, "speedup": 2.0,
        },
        "platform": "cpu",
    }
    assert bench._counts_only(detail) == {
        "requests": 6, "output_tokens": 96,
        "flight": {
            "steps": 40, "tokens_per_step": 2.4, "padding_waste_pct": 20.0,
        },
        "multiturn": {"token_parity": True, "turns_hit": 8},
        "platform": "cpu",
    }


def test_peak_table_is_keyed_by_device_kind():
    """The chip the PR 23 runs reported is in the table; there is no
    default for one that is not."""
    assert bench.PEAK_BF16_TFLOPS["TPU v5 lite"] == 197.0
    assert "v5e" not in bench.PEAK_BF16_TFLOPS


def test_multiturn_schedule_is_pure_and_shaped():
    prof = dict(conversations=3, turns=2, system_len=16, user_len=8)
    a = bench.multiturn_schedule(7, 1000, prof)
    b = bench.multiturn_schedule(7, 1000, prof)
    assert a == b                     # cold/hit passes replay identically
    system, users = a
    assert len(system) == 16
    assert len(users) == 3 and all(len(c) == 2 for c in users)
    assert all(len(u) == 8 for c in users for u in c)
    assert bench.multiturn_schedule(8, 1000, prof) != a


def test_summarize_multiturn_pairs_cold_and_hit():
    cold = [
        {"ttft_ms": 100.0, "reused": 0, "output_ids": [1, 2]},
        {"ttft_ms": 120.0, "reused": 0, "output_ids": [3, 4]},
        {"ttft_ms": 140.0, "reused": 0, "output_ids": [5, 6]},
    ]
    hit = [
        {"ttft_ms": 95.0, "reused": 0, "output_ids": [1, 2]},    # cold turn
        {"ttft_ms": 30.0, "reused": 64, "output_ids": [3, 4]},
        {"ttft_ms": 40.0, "reused": 96, "output_ids": [5, 6]},
    ]
    s = bench.summarize_multiturn(cold, hit)
    assert s["hit_turns"] == 2 and s["total_turns"] == 3
    # paired medians: cold over the SAME turns that hit (120, 140)
    assert s["cold_ttft_ms_p50"] == 140.0
    assert s["hit_ttft_ms_p50"] == 40.0
    assert s["ttft_improvement"] == round(1 - 40.0 / 140.0, 3)
    assert s["token_parity"] is True
    assert s["prefix_tokens_reused"] == 160

    hit_bad = [dict(h) for h in hit]
    hit_bad[2] = dict(hit_bad[2], output_ids=[9, 9])
    assert bench.summarize_multiturn(cold, hit_bad)["token_parity"] is False
