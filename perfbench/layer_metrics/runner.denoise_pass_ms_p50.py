"""Median wall time of the scheduler steps whose mode was ``denoise``
(flight records, host clock round one ``Engine.step``): a step whose
program was a block pass, ``L`` rows a slot over the cache, of a model
generated by diffusion over blocks; what ``runner.decode_step_ms_p50``
is to a model that yields a token a step. A step that also ran a prefill
is of mode ``prefill``. Nothing to read where no step was one (any other
model, or a program from before the mode)."""

from perfbench.loadgen import flight_records, percentile


def read(ctx):
    durs = [r["dur_ms"] for r in flight_records(ctx) if r["mode"] == "denoise"]
    return percentile(durs, 0.5) if durs else None
