"""Everything the benchmark knows of the program under test, in one file.

The window drives the program only through these functions.  Where the
program renames or reshapes what is read here (today the scheduler's
``active`` lanes and ``finished`` list, and the token lists they hold),
this is the one file to repair.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.config import ModelConfig
from repro.serve.engine import ContinuousEngine

from .refmath import SERVE_DTYPE, seed_key


def program_config(model, conf: dict) -> ModelConfig:
    return ModelConfig(**model.program_kwargs(conf))


def build_params(cfg: ModelConfig, model, conf: dict):
    """A function seed -> the program's weights, made on the device in one
    jitted call; its tree, shapes and types are checked against the
    program's own initialiser."""
    want = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0),
                                                 dtype=SERVE_DTYPE))
    rows = want["embed"].shape[0]
    make = jax.jit(lambda key: model.program_params(conf, key, rows))
    got = jax.eval_shape(make, seed_key(0))
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError(f"{cfg.name}: the benchmark's weights do not have "
                         "the program's layout")
    return lambda seed: make(seed_key(seed))


def build_engine(cfg: ModelConfig, params, serve: dict) -> ContinuousEngine:
    return ContinuousEngine(cfg, params, kv_len=serve["kv_len"],
                            n_slots=serve["lanes"], dtype=SERVE_DTYPE,
                            paged=True, prefill_chunk=serve["prefill_chunk"],
                            block_size=serve["block_size"])


def submit(eng: ContinuousEngine, prompt: np.ndarray, max_new: int,
           rid: int) -> None:
    eng.submit(prompt.tolist(), max_new, rid=rid, arrival=eng.now)


def step(eng: ContinuousEngine) -> None:
    """One engine step; it returns once its tokens are on the host."""
    eng.run(max_steps=1)


def has_work(eng: ContinuousEngine) -> bool:
    return eng.scheduler.has_work()


def progress(eng: ContinuousEngine, cursor: list) -> list[tuple]:
    """(rid, tokens so far) of every admitted request that is running or
    has finished since the last call (``cursor`` keeps the place)."""
    done = eng.scheduler.finished
    out = [(a.request.rid, len(a.tokens)) for a in done[cursor[0]:]]
    cursor[0] = len(done)
    out += [(a.request.rid, len(a.tokens))
            for a in eng.scheduler.active.values()]
    return out


def served_tokens(eng: ContinuousEngine) -> dict:
    """rid -> the tokens served so far, of every request that has one:
    those finished, and those still in a lane when the window closed."""
    lanes = list(eng.scheduler.finished) + list(eng.scheduler.active.values())
    return {a.request.rid: list(a.tokens) for a in lanes if a.tokens}


def warm_prompt_shapes(lengths, chunk: int) -> None:
    """Run the host-side slicing the engine does on a prompt of each
    length (the chunk pieces and the padded final piece) so that none of
    those small programs compiles inside the window."""
    for n in lengths:
        item = jnp.asarray([0] * n, jnp.int32)
        for start in range(0, n, chunk):
            piece = item[start:start + chunk]
            if piece.shape[0] < chunk:
                jnp.zeros((chunk,), item.dtype).at[:piece.shape[0]].set(
                    piece).block_until_ready()
