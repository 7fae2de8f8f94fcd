"""decode_step_window_ms — layer: model step (`jit_chunk`), over the WHOLE
window and from the engine's own spans (`benchmark/device_account.py`).

Seconds the device spent on one decode step, in ms: over the window's
intervals between two stamps of the device that hold ONE chunk's decode
steps and nothing else (no admission program was enqueued ahead of the
chunk or beside it, and the chunk was enqueued behind another, so the device
went from one to the next), the median of interval / `tokens` within each
class that decides a step's cost (the rows of cache walked, the sampler's
path, the chunk's length), the classes weighted by the steps the window ran
in them. `decode_step_ms` is the same quantity from one traced second; an
interval here also holds what lies between two executions of `jit_chunk`
(the launch, the slice that chains the next chunk's tokens).

Printed: intervals used of intervals seen, the median by class, p5 and p95,
the share of reads that did not have to wait, and what decides whether the
method holds: the same estimate over the traced second's own intervals of
the traced replica (a handful) and that second's steps at their classes'
window figures, both beside the device trace's `decode_step_ms` of that
second."""

from benchmark import device_account as da, engine_spans as es, spans as sp


@es.never_raises
def read(run: dict):
    lo, hi = run["window_wall"]
    ivs = da.intervals(run, lo, hi)
    if not ivs:
        return None
    got = da.seen(run, lo, hi)
    steps = da.Steps(ivs)
    late = sum(1 for c in got if not c.waited)
    print(f"decode_step_window_ms: {steps.used} clean intervals used of "
          f"{len(ivs)} paired and {len(got)} chunks read in the window "
          f"({da.replicas(run)} replica(s)); {late} reads did not have to "
          f"wait ({100.0 * late / len(got):.1f}%); coverage "
          f"{100 * da.coverage(run, lo, hi):.1f}% of the "
          f"window's wall time", flush=True)
    if steps.mean is None:
        print("decode_step_window_ms: no interval of decode steps alone",
              flush=True)
        return None
    for k in sorted(steps.by_class, key=lambda k: -steps.steps[k])[:8]:
        print(f"decode_step_window_ms:   {da.describe(k)}: "
              f"{1e3 * steps.by_class[k]:.3f} ms a step over "
              f"{len(steps.samples[k])} intervals, {steps.steps[k]} steps "
              f"of the window in this class", flush=True)
    p5, p95 = steps.spread()
    print(f"decode_step_window_ms: {len(steps.by_class)} classes with an "
          f"estimate hold {sum(steps.steps[k] for k in steps.by_class)} of "
          f"{sum(steps.steps.values())} paired steps; p5 {1e3 * p5:.3f}, "
          f"p95 {1e3 * p95:.3f} ms a step", flush=True)
    second = da.traced_second(run)
    if second:
        own = da.Steps(da.intervals(run, *second))
        # the second's own steps at what their classes cost over the window
        mix = [(w * c.tokens, steps.of(c))
               for iv, w in da.overlapping(run, *second) for c in iv.chunks]
        n = sum(k for k, _s in mix)
        trace = sp.decode_steps(run)
        print("decode_step_window_ms: in the traced second "
              + (f"{1e3 * own.mean:.3f} ms from {own.used} clean intervals "
                 f"of its own" if own.mean is not None
                 else "no clean interval of its own")
              + (f"; its {n:.0f} steps at their classes' figures "
                 f"{1e3 * sum(k * s for k, s in mix) / n:.3f} ms" if n else "")
              + (f"; the device trace's decode_step_ms "
                 f"{1e3 * trace[1] / trace[0]:.3f} over {trace[0]} steps"
                 if trace else ""), flush=True)
    return 1e3 * steps.mean
