"""What a tree's serving programs ARE, without the chip: every chunk, prefill,
place and sampler program of every configuration in `benchmark/configs/`,
lowered for a described v5e with the dispatchers' question about the backend
answered as the chip would. For each configuration its
`repr(model_config(...))`, then one line a program: its name, the SHA-256 of
its text (`lower().as_text()`, what the compile cache's key is made of) and
`kernel` where it holds a `tpu_custom_call`. Two trees whose outputs `diff`
finds equal serve the same programs from the same compile-cache entries.

    python tools/lowered.py > /root/scratch/lowered.change
    python tools/lowered.py phi3-mini-16l trinity-mini-ep8-16l

A kernel's payload names its own file by its path, so unpack the two trees
IN TURN at one path (`git archive <commit> | tar -x -C /root/scratch/tree`;
the working tree: `git ls-files -co --exclude-standard -z | tar --null -T -
-c | tar -x -C ...`) and run each tree's own copy of this script there.
About six minutes for the five configurations on this sandbox's CPU; nothing
runs, nothing is edited. `tests/test_v5e_compile.py` takes `build_compiled`
from here.
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def described_chips():
    """The devices of a `v5e:2x2` that is described, not attached."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices


def build_compiled(chip, cfg, *, max_batch: int, decode_chunk: int,
                   mesh=None):
    """An engine's compiled programs for `chip`, from shapes: no parameter
    is made, no thread started, nothing placed on a device. With a `mesh`
    (of one axis, `tp`) the engine is given it and its parameters are
    sharded over it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from ray_tpu.llm.engine import ContinuousEngine
    from ray_tpu.llm.sampler import _make_sampler
    from ray_tpu.models.published import model_config
    from ray_tpu.models.transformer import Transformer, param_specs

    eng = object.__new__(ContinuousEngine)
    eng.cfg, eng.max_batch, eng.decode_chunk, eng.mesh = (
        cfg, max_batch, decode_chunk, mesh)
    eng.model = Transformer(model_config(cfg))
    eng._sampler = _make_sampler(cfg.vocab_size)
    eng._jax, eng._jnp = jax, jnp
    shapes = jax.eval_shape(lambda: eng.model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    where = lambda spec: (  # noqa: E731
        SingleDeviceSharding(chip) if mesh is None else NamedSharding(
            mesh, P(*[axis if axis == "tp" else None for axis in spec])))
    eng.params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                             sharding=where(spec)),
        shapes, param_specs({"params": shapes})["params"])
    eng._build_compiled()
    return eng


def programs(eng, chip):
    """`(name, lowered)` of every program `eng` can be asked for: a chunk
    of each power of two up to `decode_chunk`, greedy and sampled, with the
    bound and the live rows the scheduler hands it; the prefill of every
    bucket and the hand-over of its slices; the first token's sampler."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def on_chip(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(chip))

    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda leaf: on_chip(leaf.dtype, *leaf.shape), tree)
    cache = placed(eng._cache_spec)
    for n in (1 << i for i in range(eng.decode_chunk.bit_length())):
        for greedy in (True, False):
            yield (f"chunk {n} {'greedy' if greedy else 'sampled'}",
                   eng._chunk.lower(*eng._chunk_shapes(
                       eng.params, cache, greedy, n)))
    b = eng.max_batch
    # (by blocks a row's "next token" is its whole state, a prefill hands on
    # its slices alone, and no first token is sampled: `llm/engine.py`)
    first = (eng._blocks + 3,) if eng._blocks else ()
    mirrors = (on_chip(i32, b, *first), on_chip(i32, b), on_chip(u32, b, 2),
               on_chip(f32, b), on_chip(i32, b), on_chip(f32, b))
    for bucket in sorted({eng._bucket(n)
                          for n in range(1, eng.cfg.max_seq + 1)}):
        toks = on_chip(i32, 1, bucket)
        yield (f"prefill {bucket} {eng._prefill_form(bucket)}",
               eng._prefill.lower(eng.params, toks, on_chip(i32)))
        yield f"place {bucket}", eng._place.lower(
            cache, placed(eng._slice_shapes(bucket)), mirrors,
            on_chip(i32, *first), on_chip(u32, 2), on_chip(i32, 3),
            on_chip(f32, 2))
    if eng._blocks:
        return
    yield "sample1", eng._sample1.lower(
        on_chip(f32, eng.cfg.vocab_size), on_chip(u32, 2), on_chip(f32),
        on_chip(i32), on_chip(f32))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*",
                    help="names under benchmark/configs/ (default: all)")
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # the chip is described

    import jax

    from ray_tpu.llm import LLMConfig
    from ray_tpu.models.published import model_config
    from ray_tpu.ops import attention

    attention.on_tpu = lambda: True
    chip = described_chips()[0]
    paths = sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs",
                                          "*.json")))
    for path in paths:
        with open(path) as f:
            config = json.load(f)
        if args.configs and config["name"] not in args.configs:
            continue
        cfg, app = LLMConfig(**config["llm_config"]), config["app_kwargs"]
        print(f"# {config['name']}\nconfig {model_config(cfg)!r}", flush=True)
        eng = build_compiled(chip, cfg, max_batch=app["max_batch"],
                             decode_chunk=app["decode_chunk"])
        for name, lowered in programs(eng, chip):
            text = lowered.as_text()
            print(name, hashlib.sha256(text.encode()).hexdigest(),
                  "kernel" if "tpu_custom_call" in text else "-", flush=True)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
