"""What the readers of the expert layers' counters share. The engine counts,
on the device and inside each decode chunk, the rows routed to each expert it
holds; the counts ride to the host with the chunk's tokens and come out as
attributes of the `engine.host_sync` span that read them: `moe_rows` (over
all held experts and expert layers), `moe_rows_busiest` (the same for the
busiest held expert) and `moe_steps` (the chunk's decode steps). A program
without expert layers, or from before they were counted, writes none: the
readers then return None.

`moe_touched`, beside them, is the contract the four expert rooflines read
and a later program PR sows against (PR 42's meaning, whatever the router):
the held experts that got AT LEAST ONE ROW, summed over the expert layers
and over the steps of the chunk, counted over every slot of the batch as
`moe_rows` is. A held expert that got no row need not be read, whatever
implements the step, so a step's least bytes hold one expert's weights for
each expert touched (`touched_per_step`); a program that writes no
`moe_touched` has every held expert counted as read, and one whose
`moe_touched` contradicts its other counts gets no roofline share at all."""

from __future__ import annotations

from benchmark import spans as sp


def chunks(run: dict) -> list[dict]:
    """Attributes of the window's host syncs that read a counted chunk."""
    lo, hi = run["window_wall"]
    return [s["at"] for s in sp.named(run.get("spans") or [],
                                      "engine.host_sync", lo, hi)
            if (s.get("at") or {}).get("moe_steps")]


def totals(run: dict):
    """(rows, busiest expert's rows, steps) over the window, or None."""
    got = chunks(run)
    if not got:
        return None
    return (sum(c["moe_rows"] for c in got),
            sum(c["moe_rows_busiest"] for c in got),
            sum(c["moe_steps"] for c in got))


def traced(run: dict, key: str) -> list[dict]:
    """The counted chunks that carry `key` and whose dispatch lay in the
    profiler's window (by the chunk's ordinal `seq`, which its dispatch and
    its read both carry); the whole window's where none can be matched."""
    seqs = {c["at"].get("seq") for c in sp.traced_chunks(run)} - {None}
    mine = [c for c in chunks(run) if key in c]
    return [c for c in mine if c.get("seq") in seqs] or mine


def touched_per_step(run: dict, held: int, max_batch: int):
    """{touched, steps}: the held experts a decode step touched, summed over
    the expert layers (`moe_touched` over `moe_steps` of the chunks `traced`
    gives), and the steps that was counted over. None where no counted chunk
    carries `moe_touched`, and None where a chunk's count contradicts the
    counts beside it: more than `held` (held experts x expert layers) a
    step, more than the rows routed (a touched expert got a row), or fewer
    than the rows over `max_batch` (an expert gets at most one row a slot).
    The program under judgement hands the roofline this count, so it is
    held against the counts the roofline already trusts."""
    got = traced(run, "moe_touched")
    if not got:
        return None
    for c in got:
        t, rows, steps = c["moe_touched"], c["moe_rows"], c["moe_steps"]
        if not 0 <= t <= min(held * steps, rows) or t * max_batch < rows:
            print(f"moe_spans: chunk {c.get('seq')} says {t} held experts "
                  f"touched over {steps} steps beside {rows} rows, {held} "
                  f"held a step and {max_batch} slots: they contradict one "
                  f"another, so there is no count to go by", flush=True)
            return None
    steps = sum(c["moe_steps"] for c in got)
    return {"touched": sum(c["moe_touched"] for c in got) / steps,
            "steps": steps}


def least_step(run: dict, max_batch: int, min_seconds):
    """(least, all_held, said) for a reader of an expert roofline:
    `min_seconds(touched)` is its shapes module's `decode_step_min_seconds`
    with everything but the experts touched a step filled in. `all_held` is
    the least step with every held expert read, `said` what the program
    says its steps touched (`touched_per_step`) and `least` the step counted
    on that; `all_held` itself, and `said` None, where the program says
    nothing. None where what it says contradicts its other counts: a reader
    then has no share to give."""
    all_held = min_seconds(None)
    if not traced(run, "moe_touched"):
        return all_held, all_held, None
    said = touched_per_step(run, all_held["held"], max_batch)
    if said is None:
        return None
    return min_seconds(said["touched"]), all_held, said


def step_said(least: dict, all_held: dict, said: dict | None,
              step_seconds: float) -> str:
    """What `least_step` found, in a reader's printed words: the least
    step and its bytes by part, the experts counted as read beside those
    held, and what the share would be counted on all held, so that the four
    expert rooflines can be read on one scale."""
    parts = ", ".join(f"{k} {v / 1e9:.3f}" for k, v in sorted(
        least["parts"].items(), key=lambda kv: -kv[1]))
    counted = (f"all {least['held']} held experts counted as read a step "
               f"(no count of those touched to go by)" if said is None else
               f"{least['touched']:.2f} of {least['held']} held experts "
               f"touched a step (over {said['steps']} counted steps)")
    return (f"least step {least['seconds'] * 1e3:.3f} ms "
            f"({least['bytes'] / 1e9:.3f} GB, {least['flops'] / 1e12:.3f} "
            f"TFLOP, bound by {least['bound']}); GB by part: {parts}; "
            f"{counted}; counted on all held it would be "
            f"{all_held['seconds'] * 1e3:.3f} ms, "
            f"{100.0 * all_held['seconds'] / step_seconds:.4f}%")
