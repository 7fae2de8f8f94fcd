"""decode_step_ms — layer: model step (`jit_chunk`).

Device time of one decode step, in ms: the device seconds of the `jit_chunk`
executions in the traced window over the steps those executions made. Both
come from the device trace, so both are on one clock: inside an execution
every operation of the scan's body appears once per step under one name, and
the most often repeated name counts the steps (`trace_reduce.loop_steps`).
The program's own count, the `tokens` of the `engine.dispatch_chunk` spans
dispatched while the profiler ran, is printed beside it; it is on the host's
clock, up to 4 chunks ahead of the device, so the two agree only roughly."""

from benchmark import spans as sp


def read(run: dict):
    got = sp.decode_steps(run)
    if got is None:
        return None
    steps, secs = got
    by_spans = sum(c["at"]["tokens"] for c in sp.traced_chunks(run))
    print(f"decode_step_ms: {steps} steps in {secs:.4f}s of jit_chunk on "
          f"the device; the engine's spans count {by_spans} steps "
          f"dispatched while the profiler ran", flush=True)
    return 1000.0 * secs / steps
