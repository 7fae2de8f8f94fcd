"""The engine's table of serving programs and the list a start leaves the
next (`ray_tpu/llm/programs.py`; README "Serving hot loop"): every program is
built by one route and kept in one table; a start that finds the last start's
list builds it AHEAD, in that start's order, and serves the same tokens from
the same program texts. CPU, toy sizes, a temporary directory for the lists
(`compile_cache.lists_dir` answers it): nothing here is a device number."""

import hashlib
import json
import os
import threading
import time

import pytest

from ray_tpu._private import compile_cache
from ray_tpu.llm import LLMConfig, programs
from ray_tpu.llm.engine import ContinuousEngine, SamplingParams

CFG = LLMConfig(vocab_size=128, d_model=32, n_layers=1, n_heads=2, max_seq=64)
WAIT_S = 120.0


def until(cond, what: str):
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


# ----------------------------------------------------------- the table alone
class Lowered:
    """What `lower(key)` hands the pool: something with a `compile`."""

    def __init__(self, key, log, gate=None):
        self.key, self.log, self.gate = key, log, gate

    def compile(self):
        if self.gate is not None:
            assert self.gate.wait(timeout=WAIT_S)
        if self.key[0] == "broken":
            raise ValueError(f"{self.key} does not compile")
        self.log.append(("compiled", self.key))
        return ("program", self.key)


def table(tmp_path, identity="this start", gate=None, slow=0.0):
    log = []

    def lower(key):
        assert threading.current_thread().name == "rt-llm-lower"
        time.sleep(slow)
        if key[0] == "untraceable":
            raise TypeError(f"{key} does not trace")
        log.append(("lowered", key))
        return Lowered(key, log, gate)

    return programs.ProgramTable(lower, str(tmp_path), identity), log


def listed(tmp_path, identity="this start"):
    with open(os.path.join(tmp_path, programs.list_name(identity))) as f:
        return [tuple(key) for key in json.load(f)["programs"]]


def test_a_key_is_built_once_on_demand_and_is_on_the_list(tmp_path):
    tab, log = table(tmp_path)
    try:
        assert tab.build_ahead() == 0  # no list: nothing ahead
        keys = [("chunk", 4, False), ("prefill", 8), ("chunk", 4, False)]
        assert [tab.get(k) for k in keys] == [("program", k) for k in keys]
        assert log == [("lowered", keys[0]), ("compiled", keys[0]),
                       ("lowered", keys[1]), ("compiled", keys[1])]
        assert tab.stats() == {"programs_ahead": 0, "programs_waited": 0,
                               "programs_on_demand": 2, "list_unused": 0}
        assert listed(tmp_path) == keys[:2] == tab.keys()
    finally:
        tab.close()


def test_the_list_is_built_ahead_in_its_order_by_one_lowering_thread(
        tmp_path):
    keys = [("chunk", 4, False), ("prefill", 8), ("sample1",), ("place", 8),
            ("chunk", 2, True), ("chunk", 1, True)]
    first, _ = table(tmp_path)
    for k in keys:
        first.get(k)
    first.close()
    tab, log = table(tmp_path)
    try:
        assert tab.build_ahead() == len(keys)
        until(lambda: len(log) == 2 * len(keys), "the list to be built")
        assert [k for what, k in log if what == "lowered"] == keys
        # asked in another order, one of them new: the list is THIS start's
        asked = [keys[1], keys[0], ("prefill", 16), keys[3]]
        assert [tab.get(k) for k in asked] == [("program", k) for k in asked]
        assert tab.stats() == {"programs_ahead": 3, "programs_waited": 0,
                               "programs_on_demand": 1, "list_unused": 3}
        assert listed(tmp_path) == asked
    finally:
        tab.close()


def test_a_key_the_list_lacks_is_lowered_next_and_a_listed_one_is_waited_for(
        tmp_path):
    keys = [("chunk", n, False) for n in (8, 4, 2, 1)]
    first, _ = table(tmp_path)
    for k in keys:
        first.get(k)
    first.close()
    tab, log = table(tmp_path, slow=0.05)
    try:
        tab.build_ahead()
        new = ("prefill", 8)
        assert tab.get(new) == ("program", new)  # jumps what is left
        assert tab.get(keys[-1]) == ("program", keys[-1])  # waits its turn
        order = [k for what, k in log if what == "lowered"]
        assert order.index(new) < order.index(keys[-1]) == len(keys)
        got = tab.stats()
        assert got["programs_on_demand"] == 1
        assert got["programs_ahead"] + got["programs_waited"] == 1
    finally:
        tab.close()


@pytest.mark.parametrize("left", [
    b'{"identity": "this start", "programs": [["chunk", 4, fal',  # torn
    json.dumps({"identity": "another start",  # foreign: not this start's
                "programs": [["chunk", 4, False]]}).encode(),
    json.dumps({"programs": "chunk"}).encode(),  # not a list at all
    json.dumps(["chunk", 4]).encode(),
], ids=["torn", "foreign", "no_identity", "not_an_object"])
def test_a_list_that_is_not_this_starts_is_ignored_and_rewritten(
        tmp_path, left):
    path = os.path.join(tmp_path, programs.list_name("this start"))
    with open(path, "wb") as f:
        f.write(left)
    tab, log = table(tmp_path)
    try:
        assert tab.build_ahead() == 0 and log == []
        assert tab.get(("sample1",)) == ("program", ("sample1",))
        assert listed(tmp_path) == [("sample1",)]
        assert tab.stats()["programs_on_demand"] == 1
    finally:
        tab.close()


def test_a_listed_key_that_fails_to_build_is_dropped_and_built_when_asked(
        tmp_path):
    bad = [("untraceable", 1), ("broken", 2)]
    with open(os.path.join(tmp_path, programs.list_name("this start")),
              "w") as f:
        json.dump({"identity": "this start",
                   "programs": [list(k) for k in bad] + [["sample1"]]}, f)
    tab, log = table(tmp_path)
    try:
        assert tab.build_ahead() == 3
        until(lambda: ("compiled", ("sample1",)) in log, "the list's rest")
        assert tab.get(("sample1",)) == ("program", ("sample1",))
        # nothing but the gain was lost: asked for, each is built again, on
        # demand, and ITS failure is the asker's to see
        with pytest.raises(TypeError, match="does not trace"):
            tab.get(bad[0])
        with pytest.raises(ValueError, match="does not compile"):
            tab.get(bad[1])
        assert tab.stats()["programs_on_demand"] == 2
    finally:
        tab.close()


def test_no_directory_no_list(tmp_path):
    tab = programs.ProgramTable(lambda key: Lowered(key, []), "", "a start")
    try:
        assert tab.build_ahead() == 0
        assert tab.get(("sample1",)) == ("program", ("sample1",))
        assert os.listdir(tmp_path) == []
    finally:
        tab.close()


def test_never_more_compiles_at_once_than_the_pool_is_wide(tmp_path):
    keys = [("chunk", n, False) for n in range(1, 11)]
    first, _ = table(tmp_path)
    for k in keys:
        first.get(k)
    first.close()
    gate, running, most = threading.Event(), [0], [0]
    lock = threading.Lock()

    class Counted(Lowered):
        def compile(self):
            with lock:
                running[0] += 1
                most[0] = max(most[0], running[0])
            try:
                return super().compile()
            finally:
                with lock:
                    running[0] -= 1

    log = []
    tab = programs.ProgramTable(lambda key: Counted(key, log, gate),
                                str(tmp_path), "this start")
    try:
        assert tab.build_ahead() == len(keys)
        until(lambda: running[0] == programs.POOL_WIDTH, "the pool to fill")
        time.sleep(0.1)  # the lowering thread has run on: nothing more starts
        assert running[0] == most[0] == programs.POOL_WIDTH
        gate.set()
        assert tab.get(keys[-1]) == ("program", keys[-1])
        assert most[0] == programs.POOL_WIDTH
    finally:
        gate.set()
        tab.close()


def test_close_during_a_build_ahead_returns_and_tells_whoever_waits(
        tmp_path):
    keys = [("chunk", n, False) for n in (8, 4, 2, 1)]
    first, _ = table(tmp_path)
    for k in keys:
        first.get(k)
    first.close()
    gate = threading.Event()  # no compile ever ends by itself
    tab, log = table(tmp_path, gate=gate)
    told = []

    def wait():
        try:
            tab.get(keys[0])
        except RuntimeError as e:
            told.append(str(e))

    try:
        tab.build_ahead()
        waiter = threading.Thread(target=wait, daemon=True)
        waiter.start()
        until(lambda: ("lowered", keys[-1]) in log, "the list to be lowered")
        t0 = time.monotonic()
        tab.close()
        waiter.join(timeout=WAIT_S)
        assert time.monotonic() - t0 < 5.0 and not waiter.is_alive()
        assert told == ["the engine's programs are shut down"]
        assert not tab._thread.is_alive()
        with pytest.raises(RuntimeError, match="shut down"):
            tab.get(("sample1",))
    finally:
        gate.set()  # the pool's threads go home


# ------------------------------------------------------- the engine's starts
GREEDY = SamplingParams(temperature=0.0, max_tokens=7)
SAMPLED = SamplingParams(temperature=0.8, top_k=8, top_p=0.9, max_tokens=7,
                         seed=59)
PROMPTS = [[1, 2, 3], list(range(1, 12))]  # buckets 8 and 16


def texts_of(monkeypatch_ctx, seen):
    """Every lowering's text, locations and all, by key."""
    lower = ContinuousEngine._lower

    def spy(self, key):
        lowered = lower(self, key)
        seen[key] = hashlib.sha256(
            lowered.as_text(debug_info=True).encode()).hexdigest()
        return lowered

    monkeypatch_ctx.setattr(ContinuousEngine, "_lower", spy)


def served(eng):
    return [eng.submit(p, sp).tokens() for sp in (GREEDY, SAMPLED)
            for p in PROMPTS]


@pytest.fixture(scope="module")
def starts(tmp_path_factory):
    """Two starts of one engine on one directory of lists: the first finds
    none and builds on demand, the second finds the first's and builds
    ahead. What each lowered, counted and served."""
    lists = str(tmp_path_factory.mktemp("lists"))
    out = {"lists": lists}
    for name in ("on_demand", "from_list"):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(compile_cache, "lists_dir", lambda: lists)
            texts: dict = {}
            texts_of(mp, texts)
            eng = ContinuousEngine(CFG, max_batch=2, decode_chunk=2)
            try:
                if name == "from_list":
                    want = set(out["on_demand"]["keys"])
                    until(lambda: want <= {
                        k for k, e in eng._programs._entries.items()
                        if e.built}, "the list to be built ahead")
                    out["before"] = eng.program_stats()
                tokens = served(eng)
                out[name] = {"tokens": tokens, "texts": dict(texts),
                             "keys": eng._programs.keys(),
                             "stats": eng.program_stats()}
                if name == "from_list":
                    # a shape the list lacks: a longer prompt's bucket
                    eng.submit(list(range(1, 30)), GREEDY).tokens()
                    out["late"] = eng.program_stats()
                    out["late_keys"] = eng._programs.keys()
            finally:
                eng.shutdown()
            (out["file"],) = os.listdir(lists)
            with open(os.path.join(lists, out["file"])) as f:
                out[name]["list"] = [tuple(k)
                                     for k in json.load(f)["programs"]]
    return out


def test_a_start_without_a_list_builds_every_program_on_demand(starts):
    got = starts["on_demand"]
    assert got["stats"] == {
        "programs_ahead": 0, "programs_waited": 0,
        "programs_on_demand": len(got["keys"]), "list_unused": 0}
    kinds = {key[0] for key in got["keys"]}
    assert kinds == {"probe", "chunk", "prefill", "place", "sample1"}
    assert got["keys"][0] == ("probe",)
    assert got["keys"][1] == ("chunk", 2, False)  # `_count_boundary_copies`
    assert len(set(got["keys"])) == len(got["keys"]) == len(got["texts"])


def test_a_second_start_builds_every_listed_program_before_the_first_request(
        starts):
    first, second = starts["on_demand"], starts["from_list"]
    # before any request: the two programs the constructor takes itself
    assert starts["before"]["programs_on_demand"] == 0
    assert (starts["before"]["programs_ahead"]
            + starts["before"]["programs_waited"]) == 2
    # and the requests built nothing: every program they asked for was there
    assert second["stats"]["programs_on_demand"] == 0
    assert second["stats"]["list_unused"] == 0
    assert second["stats"]["programs_waited"] == starts["before"][
        "programs_waited"]
    assert second["stats"]["programs_ahead"] == len(first["keys"]) - starts[
        "before"]["programs_waited"]
    assert set(second["texts"]) == set(first["keys"])


@pytest.mark.parametrize("which", range(4), ids=[
    "greedy_bucket_8", "greedy_bucket_16", "sampled_bucket_8",
    "sampled_bucket_16"])
def test_the_tokens_are_the_same_with_and_without_a_list(starts, which):
    assert (starts["from_list"]["tokens"][which]
            == starts["on_demand"]["tokens"][which])
    assert len(starts["on_demand"]["tokens"][which]) == 7


def test_a_start_from_a_list_lowers_the_texts_a_start_on_demand_does(starts):
    """The order of first traces held: a text names whoever first traced a
    jitted helper, so one lowering thread goes through the list in the last
    start's order. Locations included (`debug_info`)."""
    first, second = starts["on_demand"], starts["from_list"]
    assert list(second["texts"]) == list(first["texts"]) == first["keys"]
    assert second["texts"] == first["texts"]


def test_the_list_is_this_starts_and_a_key_it_lacked_is_on_the_next(starts):
    first, second = starts["on_demand"], starts["from_list"]
    assert first["list"] == first["keys"]
    late = [k for k in starts["late_keys"] if k not in second["keys"]]
    assert late == [("prefill", 32), ("place", 32)]
    assert starts["late"]["programs_on_demand"] == 2
    assert second["list"] == starts["late_keys"]
    assert set(second["list"]) == set(first["list"]) | set(late)


def test_another_batch_or_configuration_is_another_list(starts, monkeypatch):
    """A stale list, left by a start with another `max_batch` or another
    model, is not this start's: it is ignored, and this start writes its
    own beside it. Shut down at once, while whatever it builds is in
    flight: `shutdown` returns."""
    monkeypatch.setattr(compile_cache, "lists_dir", lambda: starts["lists"])
    other = LLMConfig(vocab_size=128, d_model=32, n_layers=1, n_heads=2,
                      max_seq=32)
    for cfg, batch in ((CFG, 3), (other, 2)):
        eng = ContinuousEngine(cfg, max_batch=batch, decode_chunk=2)
        t0 = time.monotonic()
        eng.shutdown()
        assert time.monotonic() - t0 < 15.0
        assert eng.program_stats()["programs_ahead"] == 0
        assert eng.program_stats()["programs_on_demand"] == 2
    assert len(os.listdir(starts["lists"])) == 3
    assert starts["file"] in os.listdir(starts["lists"])


def test_shutdown_during_a_build_ahead_returns(starts, monkeypatch):
    monkeypatch.setattr(compile_cache, "lists_dir", lambda: starts["lists"])
    eng = ContinuousEngine(CFG, max_batch=2, decode_chunk=2)
    assert eng._programs._todo or eng._programs.stats()["list_unused"]
    t0 = time.monotonic()
    eng.shutdown()
    assert time.monotonic() - t0 < 15.0
    assert not eng._programs._thread.is_alive()
    assert not any(t.is_alive() for t in eng._threads)
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit([1, 2, 3], GREEDY)


def test_the_abstract_arguments_are_what_the_sites_pass(monkeypatch):
    """No `jax.jit` dispatch is left on a serving call, so what the table
    lowers from has to be EXACTLY what each site passes: for every program
    a greedy and a sampled request ask for, the text lowered from the call's
    own arguments (as `jax.jit` would have at that call) is the text the
    table lowered from shapes."""
    from ray_tpu.llm import engine as engine_mod

    seen, texts = {}, {}
    texts_of(monkeypatch, texts)
    call = engine_mod._Kind.__call__

    def spy(self, *args):
        ints, _ = self._key_of(args)
        key = (self.name, *ints)
        if key not in seen:
            seen[key] = hashlib.sha256(self.jitted.lower(*args).as_text(
                debug_info=True).encode()).hexdigest()
        return call(self, *args)

    monkeypatch.setattr(engine_mod._Kind, "__call__", spy)
    eng = ContinuousEngine(CFG, max_batch=2, decode_chunk=2)
    try:
        served(eng)
    finally:
        eng.shutdown()
    assert {k[0] for k in seen} == {"chunk", "prefill", "place", "sample1"}
    assert seen == {key: texts[key] for key in seen}
