"""batch_occupancy — layer: engine scheduler.

Mean over the `engine.dispatch_chunk` spans of the window of `active` /
max_batch: the share of the batch's slots that held a request when a chunk
was dispatched. Every decode step runs at the fixed shape of max_batch
slots, so an empty slot is device time spent on nothing."""

from benchmark import spans as sp


def read(run: dict):
    lo, hi = run["window_wall"]
    chunks = sp.named(run["spans"], "engine.dispatch_chunk", lo, hi)
    if not chunks:
        return None
    max_batch = run["config"]["app_kwargs"]["max_batch"]
    steps = sum(c["at"]["tokens"] for c in chunks)
    print(f"batch_occupancy: {len(chunks)} chunks in the window, mean "
          f"{steps / len(chunks):.2f} steps a chunk", flush=True)
    return sum(c["at"]["active"] for c in chunks) / (len(chunks) * max_batch)
