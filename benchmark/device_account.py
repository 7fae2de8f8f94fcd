"""The device's account over the whole window, from the engine's own spans.

The device runs one engine's programs in the order they were enqueued, and
the scheduler blocks on the OLDEST chunk in flight, usually before it is
done. So the instant that read returns (`block_ready` on `engine.host_sync`,
true to the device only where `block_waited` says the read had to wait) is
the instant the chunk finished on the device, on the host's clock: a STAMP.
The read of a hand-over's first token that had to wait (`firsts_ready`,
`firsts_waited`) is a stamp too: the instant the prefill behind it was done,
which lies after the last chunk enqueued before that prefill
(`engine.prefill` `after_seq`, tied to the read by `engine.first_token`
`sync_seq`). Between two stamps the device ran exactly what was enqueued
between them: the chunks by their ordinals (`seq`) and what each
`engine.dispatch_chunk` counts ahead of itself (`prefill_buckets_ahead`,
`places_ahead`; `in_flight` 0: the pipeline was dry when the chunk was
enqueued; `prefills_beside`: a prefill's place is unsure by this chunk, which
then parts no intervals). That is an account of all of the window where the
device trace is one second of it.

What the four readers of `layer_metrics/` share: the chunks joined by their
ordinal, the intervals between stamps, the cost of a decode step by the
class that decides it, an interval's excess over its steps, what a prefill
of each bucket costs where the excess is that and nothing else and so the
admission programs' part of any excess (`Admissions`: the rest is the device
standing idle at a hand-over), the dry gaps, and the same sums over the device trace's own second so that each
estimate can be held against the trace. A program that does not write the
attributes gives no chunks and every reader returns None.

Run as a program of its own, held to the CPU, it lists the executions of
`jit_chunk` in a trace on the trace's clock (reading a trace imports JAX, and
the benchmark's own process never does). That is the by-hand tool behind
PERF.md's figure for how late `block_ready` is: the stamps less
`host_phases`' `wall_offset_ns`, against the execution that ended last
before each.

    JAX_PLATFORMS=cpu python benchmark/device_account.py <file.xplane.pb>
"""

from __future__ import annotations

import bisect
import json
import os
import sys
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import spans as sp, stats  # noqa: E402

#: Below this share of the window's wall seconds inside paired intervals the
#: account does not speak for the window.
MIN_COVERAGE = 0.5
_KEY = "device_account"  # where a run keeps what was joined


@dataclass
class Chunk:
    """One decode chunk: what its dispatch said and what its read saw."""
    pid: int
    seq: int
    tokens: int
    klass: tuple         # what decides a step's cost
    in_flight: int       # chunks in flight when it was enqueued
    buckets: dict        # bucket -> prefills of it enqueued ahead
    places: int          # `place` programs enqueued ahead
    beside: bool         # a prefill's call ran beside its dispatch
    dispatched: float    # start of its dispatch, wall seconds
    ready: float | None = None    # its block was on the host
    waited: bool = False          # ... and that read had to wait
    firsts_ready: float | None = None
    firsts_waited: bool = False
    sync_end: float | None = None

    @property
    def prefills(self) -> int:
        return sum(self.buckets.values())


@dataclass
class Interval:
    """What the device ran between two stamps: `chunks`, in their order
    (none where a prefill alone lies between a block and its first token),
    and the admission programs counted here. `dry` is the part of it
    before the dispatch of a chunk enqueued into an empty pipeline (the
    device had no chunk of this engine's to run; it may have had a
    prefill), and `wait` the part of THAT in which the scheduler waited
    for work, nobody being seated."""
    pid: int
    chunks: tuple
    start: float
    seconds: float
    buckets: dict = field(default_factory=dict)
    places: int = 0
    dry: float = 0.0
    wait: float = 0.0

    @property
    def end(self) -> float:
        return self.start + self.seconds

    @property
    def tokens(self) -> int:
        return sum(c.tokens for c in self.chunks)

    @property
    def prefills(self) -> int:
        return sum(self.buckets.values())

    @property
    def prefill_rows(self) -> int:
        return sum(b * k for b, k in self.buckets.items())

    @property
    def admissions(self) -> int:
        return self.prefills + self.places

    @property
    def clean(self) -> bool:
        """One chunk's decode steps and nothing else, back to back."""
        return (len(self.chunks) == 1 and not self.admissions
                and self.chunks[0].in_flight > 0)


def parse_buckets(text: str) -> dict:
    """`"4096:2,6144:1"` -> {4096: 2, 6144: 1}."""
    return {int(b): int(k) for b, k in
            (part.split(":") for part in (text or "").split(",") if part)}


def klass_of(at: dict) -> tuple:
    """The class that decides what a step of the chunk costs: the rows of
    cache its attention walks by kind of leaf, the sampler's path, and the
    chunk's length (an execution's fixed part is shared by its steps)."""
    return (at.get("kv_rows_full", at.get("kv_rows")),
            at.get("kv_rows_window"), at.get("sampler"), at["tokens"])


def chunks(run: dict) -> dict[int, list[Chunk]]:
    """pid -> the replica's chunks that carry an ordinal, in its order, each
    with what the `engine.host_sync` of the same ordinal saw. Empty for a
    program that writes no ordinals."""
    if _KEY in run:
        return run[_KEY]
    out: dict[int, dict[int, Chunk]] = {}
    spans = run.get("spans") or []
    for s in spans:
        at = s.get("at") or {}
        if s.get("n") != "engine.dispatch_chunk" or "seq" not in at:
            continue
        out.setdefault(s["pid"], {})[at["seq"]] = Chunk(
            pid=s["pid"], seq=at["seq"], tokens=at["tokens"],
            klass=klass_of(at), in_flight=at.get("in_flight", 0),
            buckets=parse_buckets(at.get("prefill_buckets_ahead", "")),
            places=at.get("places_ahead", 0),
            beside=bool(at.get("prefills_beside")), dispatched=s["a"])
    for s in spans:
        at = s.get("at") or {}
        if s.get("n") != "engine.host_sync" or "block_ready" not in at:
            continue
        c = out.get(s["pid"], {}).get(at.get("seq"))
        if c is None or c.tokens != at.get("tokens"):
            continue  # a read of a chunk nobody saw dispatched
        c.ready, c.waited = at["block_ready"], bool(at.get("block_waited"))
        c.firsts_ready = at.get("firsts_ready")
        c.firsts_waited = bool(at.get("firsts_waited"))
        c.sync_end = s["b"]
    run[_KEY] = {pid: [by_seq[k] for k in sorted(by_seq)]
                 for pid, by_seq in out.items()}
    return run[_KEY]


def _waits(run: dict) -> dict[int, tuple[list, list]]:
    """pid -> (starts, seconds waited before) of the scheduler's passes: a
    pass's `idle_ms` is what the loop waited for work since the pass
    before it."""
    key = _KEY + "_waits"
    if key not in run:
        by_pid: dict = {}
        for s in run.get("spans") or []:
            at = s.get("at") or {}
            if s.get("n") == "engine.iteration" and at.get("idle_ms"):
                by_pid.setdefault(s["pid"], []).append(
                    (s["a"], at["idle_ms"] / 1e3))
        run[key] = {p: tuple(zip(*sorted(v))) for p, v in by_pid.items()}
    return run[key]


def dry_gap(run: dict, before: Chunk, chunk: Chunk) -> tuple[float, float]:
    """(seconds, of them waited for work) between the chunk before being
    done and this one's dispatch beginning, for a chunk enqueued into an
    empty pipeline; (0, 0) for any other."""
    if chunk.in_flight or before is None or before.ready is None:
        return 0.0, 0.0
    gap = max(0.0, chunk.dispatched - before.ready)
    starts, waited = _waits(run).get(chunk.pid, ((), ()))
    lo = bisect.bisect_right(starts, before.ready)
    hi = bisect.bisect_right(starts, chunk.dispatched)
    return gap, min(gap, sum(waited[lo:hi]))


def _first_token_stamps(run: dict, by_seq: dict) -> list[tuple]:
    """[(pid, a, instant, buckets)]: reads of first tokens that had to
    wait, each the instant the device was done with chunk `a` and then with
    the `buckets` of prefills enqueued right after it. Only where that
    place is sure: every request read there is known by its spans, no
    prefill's call ran beside the dispatch of chunk `a` or of the one after
    it, and the prefills enqueued after chunk `a` are exactly those read
    here."""
    prefill = {s["t"]: s for s in run.get("spans") or []
               if s.get("n") == "engine.prefill"
               and "after_seq" in (s.get("at") or {})}
    read_at: dict = {}
    for s in run.get("spans") or []:
        at = s.get("at") or {}
        if s.get("n") == "engine.first_token" and "sync_seq" in at:
            read_at.setdefault((s["pid"], at["sync_seq"]), []).append(
                prefill.get(s["t"]))
    out = []
    for (pid, i), found in read_at.items():
        c = by_seq.get((pid, i))
        if (c is None or not c.firsts_waited or c.firsts_ready is None
                or any(p is None or p["pid"] != pid for p in found)):
            continue
        a = max(p["at"]["after_seq"] for p in found)
        last = [p["at"]["bucket"] for p in found if p["at"]["after_seq"] == a]
        after = by_seq.get((pid, a + 1))
        before = by_seq.get((pid, a))
        if (a < i or after is None or after.prefills != len(last)
                or after.beside or (before is not None and before.beside)):
            continue
        out.append((pid, a, c.firsts_ready,
                    {b: last.count(b) for b in set(last)}))
    return out


def paired(run: dict, pid: int | None = None) -> list[Interval]:
    """Every interval between two consecutive stamps of one replica's
    device: the blocks whose read had to wait and the first tokens whose
    read had to wait (`_first_token_stamps`), with every chunk between
    them known by its dispatch and every admission program on a known side
    of both. A read that found its block ready is no
    stamp (it says when the host came, not when the device was done): the
    interval runs on to the next one that is."""
    key = _KEY + "_paired"
    if key not in run:
        by_seq = {(c.pid, c.seq): c for cs in chunks(run).values()
                  for c in cs}
        # (pid, ordinal, 0 a block / 1 the prefills after it, instant, buckets)
        # (a chunk that saw a prefill enqueued beside its own dispatch does
        # not part two intervals: which side the prefill ran on is not
        # known, so the interval runs on over the next chunk, which counts
        # that prefill ahead of itself)
        stamps = [(c.pid, c.seq, 0, c.ready, None) for c in by_seq.values()
                  if c.ready is not None and c.waited and not c.beside]
        stamps += [(p, a, 1, t, b)
                   for p, a, t, b in _first_token_stamps(run, by_seq)]
        stamps.sort(key=lambda m: m[:3])
        out = []
        for (p, a1, k1, t1, _b1), (p2, a2, k2, t2, b2) in zip(stamps,
                                                              stamps[1:]):
            cs = [by_seq.get((p, q)) for q in range(a1 + 1, a2 + 1)]
            if p != p2 or t2 < t1 or None in cs:
                continue
            iv = Interval(p, tuple(cs), t1, t2 - t1)
            for n, c in enumerate(cs):
                iv.places += c.places
                if n or not k1:  # else the stamp before lies after them
                    for b, k in c.buckets.items():
                        iv.buckets[b] = iv.buckets.get(b, 0) + k
                gap, wait = dry_gap(run, by_seq.get((p, c.seq - 1)), c)
                iv.dry += min(gap, max(0.0, c.dispatched - t1))
                iv.wait += min(wait, max(0.0, c.dispatched - t1))
            if k2:  # the prefills behind the first token read at t2
                for b, k in b2.items():
                    iv.buckets[b] = iv.buckets.get(b, 0) + k
            out.append(iv)
        run[key] = out
    return [iv for iv in run[key] if pid is None or iv.pid == pid]


def intervals(run: dict, lo: float, hi: float,
              pid: int | None = None) -> list[Interval]:
    """The intervals that END inside [lo, hi)."""
    return [iv for iv in paired(run, pid) if lo <= iv.end < hi]


def overlapping(run: dict, lo: float, hi: float,
                pid: int | None = None) -> list[tuple[Interval, float]]:
    """(interval, the share of it inside [lo, hi)) of the intervals that
    touch [lo, hi): a sum over a short stretch, such as the device trace's
    second, takes each interval by the part of it that lies there."""
    out = []
    for iv in paired(run, pid):
        part = min(iv.end, hi) - max(iv.start, lo)
        if part > 0 and iv.seconds > 0:
            out.append((iv, part / iv.seconds))
    return out


def seen(run: dict, lo: float, hi: float, pid: int | None = None) -> list:
    """The chunks read inside [lo, hi), paired or not."""
    return [c for p, cs in chunks(run).items() if pid is None or p == pid
            for c in cs if c.ready is not None and lo <= c.ready < hi]


class Steps:
    """Seconds a decode step costs, by class, from the clean intervals
    given: within a class the median of interval / tokens. `of(chunk)` is
    the class's figure, else the figure of the classes that have one
    weighted by the steps run in them (`mean`), else None."""

    def __init__(self, ivs: list[Interval]):
        self.samples: dict[tuple, list[float]] = {}
        self.steps: dict[tuple, int] = {}
        for iv in ivs:
            for c in iv.chunks:
                self.steps[c.klass] = self.steps.get(c.klass, 0) + c.tokens
            if iv.clean:
                self.samples.setdefault(iv.chunks[0].klass, []).append(
                    iv.seconds / iv.tokens)
        self.by_class = {k: stats.percentile(v, 50)
                         for k, v in self.samples.items()}
        known = sum(self.steps[k] for k in self.by_class)
        self.mean = (sum(self.by_class[k] * self.steps[k]
                         for k in self.by_class) / known if known else None)
        self.used = sum(len(v) for v in self.samples.values())

    def of(self, chunk: Chunk):
        return self.by_class.get(chunk.klass, self.mean)

    def spread(self) -> tuple[float, float] | None:
        """p5 and p95 of the clean intervals' seconds a step, each counted
        once a step."""
        flat = [s for k, v in self.samples.items() for s in v
                for _ in range(k[3])]
        if not flat:
            return None
        return stats.percentile(flat, 5), stats.percentile(flat, 95)


def excess(iv: Interval, steps: Steps) -> float | None:
    """What the interval holds beyond its chunks' own steps and beyond a
    wait for work, floored at 0: the admission programs in it and, for a
    chunk enqueued into an empty pipeline (a hand-over), whatever part of
    the host's gap no prefill filled."""
    each = [steps.of(c) for c in iv.chunks]
    if None in each:
        return None
    return max(0.0, iv.seconds - iv.wait - sum(
        c.tokens * step for c, step in zip(iv.chunks, each)))


class Admissions:
    """What the admission programs cost the device, from the intervals
    given. `seen`: bucket -> what one prefill of it cost, with its
    first-token program, in seconds, wherever that was seen alone: the
    excess a prefill of the intervals that hold prefills of that ONE bucket
    and neither a dry gap nor a wait for work. Such an interval lies
    between two chunks enqueued back to back, or between a chunk's block
    and the first token read beside it (`firsts_ready` - `block_ready`):
    the device went from program to program, so the excess is the prefills
    and nothing else. `cost`: the medians of `seen`. `idle_of_gap`: the
    share of a hand-over's dry gap that the device stood idle, where that
    can be told (the dry intervals whose buckets all have a cost: the
    median of (excess - cost) / gap, held to [0, 1]). Near 0 where the lane
    had the prefill enqueued before the chunk before was done, so that it
    ran inside the gap; near 1 where the drain waited for the prefill's
    first token and the gap followed; 1 where nothing tells. A bucket
    never seen alone takes its cost from the hand-overs that hold it and
    nothing else, less that share of their gaps (`at_hand_overs`)."""

    def __init__(self, ivs: list[Interval], steps: Steps):
        self.steps = steps
        self.seen: dict[int, list] = {}
        for iv in ivs:
            over = excess(iv, steps)
            if len(iv.buckets) == 1 and not (iv.dry or iv.wait
                                             or over is None):
                (bucket, k), = iv.buckets.items()
                self.seen.setdefault(bucket, []).append(over / k)
        self.cost = {b: stats.percentile(v, 50) for b, v in self.seen.items()}
        shares = [min(1.0, max(0.0, (excess(iv, steps) - self._priced(iv))
                               / (iv.dry - iv.wait)))
                  for iv in ivs if iv.dry - iv.wait > 0 and iv.prefills
                  and excess(iv, steps) is not None
                  and all(b in self.cost for b in iv.buckets)]
        self.idle_of_gap = stats.percentile(shares, 50) if shares else 1.0
        # a bucket never seen alone: from the hand-overs that hold it and
        # nothing else, less the idle part of their gaps
        late: dict[int, list] = {}
        for iv in ivs:
            if (len(iv.buckets) == 1 and iv.dry - iv.wait > 0
                    and not set(iv.buckets) & set(self.cost)
                    and excess(iv, steps) is not None):
                (bucket, k), = iv.buckets.items()
                late.setdefault(bucket, []).append(max(0.0, excess(
                    iv, steps) - self.idle_of_gap * (iv.dry - iv.wait)) / k)
        self.at_hand_overs = {b: stats.percentile(v, 50)
                              for b, v in late.items()}
        self.cost.update(self.at_hand_overs)

    def _priced(self, iv: Interval) -> float:
        return sum(self.cost[b] * k for b, k in iv.buckets.items())

    def programs(self, iv: Interval):
        """(seconds of the interval's excess that were admission programs,
        whether by the buckets' own cost). The prefills in it at what
        their buckets cost alone, and no more than the excess; the rest of
        the excess is the device standing idle at a hand-over. Where a
        bucket has no cost at all (it was only ever seen beside others):
        the excess less the idle part of the dry gap. A `place` is not
        told from the hole it lies in."""
        over = excess(iv, self.steps)
        if over is None or not iv.prefills:
            return over if over is None else 0.0, True
        if all(b in self.cost for b in iv.buckets):
            return min(over, self._priced(iv)), True
        return max(0.0, over - self.idle_of_gap
                   * max(0.0, iv.dry - iv.wait)), False


def replicas(run: dict) -> int:
    return max(1, len(chunks(run)))


def coverage(run: dict, lo: float, hi: float) -> float:
    """The seconds of [lo, hi) that lie between two stamps, over its wall
    seconds a replica."""
    wall = (hi - lo) * replicas(run)
    return (sum(w * iv.seconds for iv, w in overlapping(run, lo, hi)) / wall
            if wall > 0 else 0.0)


def traced_second(run: dict) -> tuple[float, float, int | None] | None:
    """(start, end, pid) of the device trace's own record in wall seconds:
    the device's first and last operation through the trace's wall offset
    (`host_phases`), else the profiler's own start and stop stamps."""
    prof = run.get("profile")
    if not prof or not prof.get("devices"):
        return None
    pid = prof.get("replica_pid")
    from benchmark import host_trace

    got = host_trace.host_phases(run)
    off = (got or {}).get("wall_offset_ns")
    if off:
        dev = prof["devices"][0]
        return ((dev["first_ns"] + off) / 1e9, (dev["last_ns"] + off) / 1e9,
                pid)
    window = sp.traced_window(run)
    return None if window is None else (*window, pid)


def traced_device(run: dict) -> tuple[float, float]:
    """(busy seconds, seconds from its first operation to its last) of the
    traced device: the idle share is 1 - busy / extent."""
    dev = run["profile"]["devices"][0]
    return dev["busy_s"], (dev["last_ns"] - dev["first_ns"]) / 1e9


def describe(klass: tuple) -> str:
    full, window, sampler, tokens = klass
    rows = f"{full}" if window is None else f"{full}+{window}"
    return f"{rows} rows/{sampler}/{tokens} steps"


# ------------------------------------------------- the trace's own chunks
def read_chunk_runs(path: str) -> dict:
    import jax

    from benchmark import trace_reduce as tr

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for ln in plane.lines:
            if ln.name != tr.MODULES_LINE:
                continue
            out += [[int(ev.start_ns), int(ev.start_ns + ev.duration_ns)]
                    for ev in ln.events
                    if tr.program_name(ev.name) == "jit_chunk"]
        break  # the benchmark traces one replica on one chip
    return {"jit_chunk": sorted(out)}


if __name__ == "__main__":
    print(json.dumps(read_chunk_runs(sys.argv[1])))
