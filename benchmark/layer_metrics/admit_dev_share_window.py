"""admit_dev_share_window — layer: model step (`jit_prefill`, `jit_sample1`),
over the WHOLE window and from the engine's own spans
(`benchmark/device_account.py`).

Share of the device's time that went to admission programs, in %: over the
window's intervals between two stamps of the device, the prefills enqueued
in each (`prefill_buckets_ahead`) at what a prefill of their bucket costs
the device, and no more than the interval's excess over its chunks' steps
(`tokens` x the step of each chunk's class, `decode_step_window_ms`'s), over
the sum of all paired intervals. What a bucket costs is observed, not
assumed: the median excess of the window's intervals that hold prefills of
that one bucket and no hand-over's hole (`device_account.Admissions`). A
bucket never seen so is priced from the hand-overs that hold it and nothing
else, less the part of their dry gaps that the window's other hand-overs
show the device idle for.
The same sum over the traced second is printed beside the trace's
`prefill_dev_share` x busy seconds over that second; a second holds 3 to 14
admissions and an interval cut by its edge is taken by its time, so the two
agree to a fifth of the trace's figure, not closer.

What is left of the excess is the device standing idle at hand-overs (the
host's side of it is `handover_gap_share_window`) and is printed beside the
trace's idle share, with the admissions counted and the coverage (paired
intervals' seconds over the window's wall seconds a replica). Below one half
the account does not speak for the window and the reader returns None."""

from benchmark import device_account as da, engine_spans as es, manifest


def sums(weighted, adm):
    """(seconds of admission programs, of the device idle at hand-overs,
    all paired seconds, intervals that went by the bound for want of a
    bucket's cost), each interval taken by its weight."""
    progs = hole = paired = 0.0
    bound = 0
    for iv, w in weighted:
        paired += w * iv.seconds
        if not iv.admissions:
            continue
        got, known = adm.programs(iv)
        if got is None:
            continue
        progs += w * got
        hole += w * (da.excess(iv, adm.steps) - got)
        bound += not known
    return progs, hole, paired, bound


@es.never_raises
def read(run: dict):
    lo, hi = run["window_wall"]
    ivs = da.intervals(run, lo, hi)
    if not ivs:
        return None
    steps = da.Steps(ivs)
    if steps.mean is None:
        print("admit_dev_share_window: no value: no interval of decode "
              "steps alone", flush=True)
        return None
    adm = da.Admissions(ivs, steps)
    cover = da.coverage(run, lo, hi)
    progs, hole, paired, bound = sums([(iv, 1.0) for iv in ivs], adm)
    print(f"admit_dev_share_window: {sum(iv.prefills for iv in ivs)} "
          f"prefills and {sum(iv.places for iv in ivs)} places in "
          f"{sum(1 for iv in ivs if iv.admissions)} of {len(ivs)} paired "
          f"intervals; admission programs {progs:.3f}s of {paired:.3f}s "
          f"paired, the device idle at hand-overs {hole:.3f}s "
          f"({100 * hole / paired:.2f}%) more; the device idles "
          f"{adm.idle_of_gap:.2f} of a hand-over's dry gap where that can "
          f"be told; buckets priced from their hand-overs "
          f"{sorted(adm.at_hand_overs)}, {bound} intervals hold one without "
          f"any price; coverage {100 * cover:.1f}%", flush=True)
    second = da.traced_second(run)
    if second:
        p, h, sec, _b = sums(da.overlapping(run, *second), adm)
        busy, extent = da.traced_device(run)
        trace = manifest.layer_reader("prefill_dev_share")(run)
        print("admit_dev_share_window: in the traced second "
              + (f"{100 * p / sec:.2f}%, idle at hand-overs "
                 f"{100 * h / sec:.2f}%, over {sec:.3f}s paired" if sec
                 else "no paired interval")
              + (f"; the device trace: prefill_dev_share {trace:.2f}% of "
                 f"busy = {trace * busy / extent:.2f}% of the second, idle "
                 f"{100 * (1 - busy / extent):.2f}%"
                 if trace is not None else ""), flush=True)
    if cover < da.MIN_COVERAGE:
        print(f"admit_dev_share_window: no value: coverage "
              f"{100 * cover:.1f}% is under {100 * da.MIN_COVERAGE:.0f}%",
              flush=True)
        return None
    return 100.0 * progs / paired
