"""The engine's table of serving programs, and the list a start leaves behind.

Every serving program of a `ContinuousEngine` is built by ONE route,
`jitted.lower(<abstract arguments>).compile()`, and kept here: key -> the
loaded program, or the future of one that is being built. A key is a
program's kind and its few integers (`("chunk", 16, False)`, `("prefill",
512)`, `("place", 512)`, `("sample1",)`), nothing else. A call site asks
`get(key)` and calls what it is given.

Two kinds of thread build. ONE traces and lowers, a key at a time, in the
order the keys were queued: tracing is Python, two tracers gain nothing,
and a program's text names whoever FIRST traced a jitted helper that a
kernel's body reuses (ROADMAP D15), so a race between two tracers would
change a Mosaic payload's debug strings from start to start, and with them
the compile cache's key. A small pool compiles what it lowered: with the
cache warm that is the entry's read and the executable's deserialisation,
which leave the interpreter to the tracer (PERF.md section 6, PR 59).

What a start asked of the table, in the order it asked, is written beside
the compile cache's entries (`_private/compile_cache.py` `lists_dir`) under
a name made of everything that decides the programs' texts. A start that
finds its list queues all of it at once (`build_ahead`) and so builds AHEAD
of the calls, in the last start's order; a key the list lacks is queued in
front of what is left of it, and is on the next list. Without a cache
directory there is no list, and every program is built when it is first
asked for: the same route, the same threads. Which of the two a start did
is in its input (the file) and its counters (`stats`), never in an option.
"""

from __future__ import annotations

import collections
import concurrent.futures
import hashlib
import json
import logging
import os
import threading
from typing import Callable, Optional

from ray_tpu._private import telemetry

logger = logging.getLogger(__name__)

#: Compiles in flight at once. The pool is fed by ONE tracer, a lowering
#: every 0.5-2 s, and a warm compile is a read of 0.3-4 s: over a warm start
#: 1.2-1.5 reads are in flight on average, so two threads keep up. A third
#: and a fourth would each pay the 2-4 s a thread's FIRST read costs on the
#: TPU client, whatever it reads, beside a tracer that is the start's
#: critical path (PERF.md section 6, PR 59). Cold (the list's entries
#: evicted) this is the most compiler runs at once, beside which the
#: replica must still answer its health check.
POOL_WIDTH = 2


def list_name(identity: str) -> str:
    """The file of the list of a start that `identity` describes."""
    return hashlib.sha256(identity.encode()).hexdigest()[:32] + ".json"


class _Entry:
    __slots__ = ("future", "listed", "asked", "built", "rec", "program")

    def __init__(self, listed: bool):
        self.future: concurrent.futures.Future = concurrent.futures.Future()
        self.listed = listed  # queued from the last start's list
        self.asked = False    # a call site has asked for it
        self.built = False    # its compile has ended
        self.rec: Optional[dict] = None  # its record in the set-up account
        self.program = None   # set once its first call has been accounted


class ProgramTable:
    """key -> program. `lower(key)` gives the key's `jax.stages.Lowered`
    and is only ever called on the table's one lowering thread;
    `directory` is where lists are kept ("" for none) and `identity` what
    this start's list is named by."""

    def __init__(self, lower: Callable, directory: str, identity: str):
        self._lower = lower
        self._identity = identity
        self._path = (os.path.join(directory, list_name(identity))
                      if directory else None)
        self._cv = threading.Condition()
        self._entries: dict = {}
        self._todo: collections.deque = collections.deque()
        self._asked: list = []  # this start's list
        self._write_lock = threading.Lock()
        self._closed = False
        self._counts = dict.fromkeys(
            ("programs_ahead", "programs_waited", "programs_on_demand"), 0)
        self._listed = self._read_list()  # before this start's asks replace it
        self._pool = concurrent.futures.ThreadPoolExecutor(
            POOL_WIDTH, thread_name_prefix="rt-llm-compile")
        self._thread = threading.Thread(target=self._lower_loop, daemon=True,
                                        name="rt-llm-lower")
        self._thread.start()

    # ------------------------------------------------------------ the list
    def _read_list(self) -> list:
        """The last start's list; a list that is missing, torn or another
        start's is no list."""
        if self._path is None:
            return []
        try:
            with open(self._path) as f:
                doc = json.load(f)
            if doc["identity"] != self._identity:
                return []
            return list(dict.fromkeys(tuple(key) for key in doc["programs"]))
        except (OSError, ValueError, KeyError, TypeError):
            return []

    def build_ahead(self) -> int:
        """Queue what the last start's list names and the table lacks, in
        the list's order. Returns how many."""
        with self._cv:
            keys = [key for key in self._listed if key not in self._entries]
            for key in keys:
                self._entries[key] = _Entry(listed=True)
            self._todo.extend(keys)
            self._cv.notify_all()
        return len(keys)

    def _write_list(self) -> None:
        """This start's list so far, in place of whatever was there."""
        with self._write_lock:  # the lane and the scheduler both ask
            with self._cv:
                doc = {"identity": self._identity,
                       "programs": [list(key) for key in self._asked]}
            try:
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                tmp = f"{self._path}.{os.getpid()}.tmp"
                with open(tmp, "w") as f:
                    json.dump(doc, f)
                os.replace(tmp, self._path)
            except OSError:
                pass  # a full or read-only disk costs the next start its list

    # ---------------------------------------------------------- call sites
    def get(self, key: tuple):
        """The program of `key`; waits while it is built. What fails to
        build raises here, and is built again when next asked for."""
        entry = self._entries.get(key)
        if entry is not None and entry.program is not None:
            return entry.program  # every call but a program's first
        with self._cv:
            if self._closed:
                raise RuntimeError("the engine's programs are shut down")
            entry = self._entries.get(key)
            if entry is None:
                # (the list may name it and not be queued yet: the probe's)
                entry = self._entries[key] = _Entry(key in self._listed)
                self._todo.appendleft(key)  # next, before the list's rest
                self._cv.notify_all()
            first = not entry.asked
            if first:
                entry.asked = True
                self._asked.append(key)
                self._counts["programs_ahead" if entry.built
                             else "programs_waited" if entry.listed
                             else "programs_on_demand"] += 1
        if first and self._path is not None:
            self._write_list()
        program = entry.future.result()
        if first:
            if entry.rec is not None:
                telemetry.ACCOUNT.first_call(entry.rec)
            entry.program = program
        return program

    def keys(self) -> list:
        """The keys asked for so far, in the order they were asked."""
        with self._cv:
            return list(self._asked)

    def stats(self) -> dict:
        """For /v1/stats `setup`: the programs built from the list before
        anyone asked (`programs_ahead`), asked for while the list's build
        had them in hand (`programs_waited`), not on the list
        (`programs_on_demand`), and built from the list and never asked for
        (`list_unused`)."""
        with self._cv:
            return {**self._counts, "list_unused": sum(
                e.listed and not e.asked for e in self._entries.values())}

    # ------------------------------------------------------------ builders
    def _lower_loop(self) -> None:
        while True:
            with self._cv:
                while not (self._todo or self._closed):
                    self._cv.wait()
                if self._closed:
                    return
                key = self._todo.popleft()
                entry = self._entries[key]
            try:
                lowered = self._lower(key)
                handed = telemetry.ACCOUNT.hand_over()
                if self._closed:
                    return
                self._pool.submit(self._compile, key, entry, lowered, handed)
            except BaseException as e:  # noqa: BLE001 - the asker's to see
                self._failed(key, entry, e)

    def _compile(self, key, entry: _Entry, lowered, handed) -> None:
        try:
            telemetry.ACCOUNT.take_over(handed)
            program = lowered.compile()
            rec = telemetry.ACCOUNT.built()
        except BaseException as e:  # noqa: BLE001 - the asker's to see
            self._failed(key, entry, e)
            return
        with self._cv:
            entry.built, entry.rec = True, rec
            if rec is not None and not entry.asked:
                telemetry.ACCOUNT.ahead(rec)
        self._resolve(entry, program, None)

    def _failed(self, key, entry: _Entry, error: BaseException) -> None:
        """A build failed: whoever waits is told, and the key is dropped,
        to be built when it is next asked for."""
        with self._cv:
            if self._entries.get(key) is entry:
                del self._entries[key]
            if key in self._listed:
                self._listed.remove(key)  # whoever asks, asks on demand
            asked = entry.asked
        if not asked:
            logger.warning("program %s of the last start's list failed to "
                           "build and is dropped: %r", key, error)
        self._resolve(entry, None, error)

    @staticmethod
    def _resolve(entry: _Entry, program, error) -> None:
        try:
            if error is None:
                entry.future.set_result(program)
            else:
                entry.future.set_exception(error)
        except concurrent.futures.InvalidStateError:
            pass  # `close` has told its waiters already

    def close(self) -> None:
        """Stop the builders: nothing more is lowered, what is queued for the
        pool is dropped, and whoever waits for a program is told. A compile
        that has begun ends on its own, for nobody."""
        with self._cv:
            self._closed = True
            self._todo.clear()
            waiting = [e for e in self._entries.values()
                       if not e.future.done()]
            self._cv.notify_all()
        self._pool.shutdown(wait=False, cancel_futures=True)
        error = RuntimeError("the engine's programs are shut down")
        for entry in waiting:
            self._resolve(entry, None, error)
        self._thread.join(timeout=10)
