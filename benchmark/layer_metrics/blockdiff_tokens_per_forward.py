"""blockdiff_tokens_per_forward — layer: model step (the chunk program of a
model that generates by diffusion over blocks, `llm/engine.py`
`_make_block_chunk`).

Answer tokens given out per forward a live slot ran, over the window:
`bd_tokens / bd_forwards` of the window's `engine.host_sync` spans. A block of
L positions costs between 2 and T + 1 forwards, so the rate lies between
L / (T + 1) and L / 2: 0.8 at L = T = 4 on random weights, where no
confidence reaches the threshold and every denoising forward frees exactly
one position (the floor of what a trained checkpoint gives). It is this
model's acceptance rate: what `tpot_p95_ms` is divided by, forward for
forward. A program that counts no `bd_forwards` gives no value."""

from benchmark import engine_spans as es, spans as sp


def counted(run: dict) -> list[dict]:
    """Attributes of the window's host syncs that read a chunk of forwards."""
    lo, hi = run["window_wall"]
    return [s["at"] for s in sp.named(run.get("spans") or [],
                                      "engine.host_sync", lo, hi)
            if (s.get("at") or {}).get("bd_forwards")]


@es.never_raises
def read(run: dict):
    got = counted(run)
    if not got:
        return None
    total = {k: sum(c.get(k, 0) for c in got)
             for k in ("bd_forwards", "bd_commits", "bd_tokens", "bd_freed")}
    print(f"blockdiff_tokens_per_forward: {total['bd_tokens']} tokens from "
          f"{total['bd_forwards']} forwards of live slots in {len(got)} "
          f"chunks; {total['bd_commits']} of them committed a block "
          f"({total['bd_forwards'] / max(1, total['bd_commits']):.2f} "
          f"forwards a block) and the others freed {total['bd_freed']} "
          f"positions", flush=True)
    return total["bd_tokens"] / total["bd_forwards"]
