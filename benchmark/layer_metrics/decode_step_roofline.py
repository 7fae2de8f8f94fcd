"""decode_step_roofline — layer: kernels (ops/decode_attention.py XLA path
and the step's matmuls).

The least time the chip could take for a decode step over the time it took
(`decode_step_ms`), in %. The least time is (weight bytes + bytes of the
valid cache rows) / the chip's memory bandwidth, from `benchmark/shapes.py`
and `benchmark/peaks.py`: at 8 sequences a step is bound by bandwidth, not by
arithmetic (the function checks, and the reader prints which). Valid rows are
taken per traced chunk as active slots x the mean context, prompt plus half
the answer, of the window's requests: an estimate, because the engine's spans
do not carry the lengths."""

from benchmark import peaks, shapes, spans as sp


def read(run: dict):
    got = sp.decode_steps(run)
    done = [r for r in run["records"] if r.ok]
    chunks = sp.traced_chunks(run)
    if got is None or not done or not chunks:
        return None
    steps, secs = got
    llm = run["config"]["llm_config"]
    context = sum(r.plen + r.n_tokens / 2 for r in done) / len(done)
    active = (sum(c["at"]["active"] * c["at"]["tokens"] for c in chunks)
              / sum(c["at"]["tokens"] for c in chunks))
    least = shapes.decode_step_min_seconds(
        llm, run["config"]["app_kwargs"]["max_batch"], active * context,
        peaks.peaks(run["device"]["kind"]))
    print(f"decode_step_roofline: least step {least['seconds'] * 1e3:.3f} ms "
          f"({least['bytes'] / 1e9:.2f} GB, bound by {least['bound']}); "
          f"{active:.2f} slots active at a mean context of {context:.0f}",
          flush=True)
    return 100.0 * least["seconds"] / (secs / steps)
