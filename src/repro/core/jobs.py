"""Job builders for the real-JAX lane executor: wrap the model zoo's train
and serve steps as schedulable grids of blocks.

A training job's block is one fixed-size microbatch optimizer step; a
serving job's block is one k-token decode chunk for a request batch.  Both
are homogeneous, which is exactly the structural property the paper's
predictor exploits.

Serve jobs of one configuration share what a server holds once: one weight
set per (configuration, weight seed) and one compiled prefill/decode pair
per (configuration, batch, prompt length, cache length).  Each job owns
only its prompt, its KV cache and the tokens it generates.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ArchConfig
from repro.models import lm
from repro.models.layers import DEFAULT_COMPUTE_DTYPE, cast
from repro.optim import adamw

from . import tracing
from .executor import ExecutorJob


def make_train_job(
    cfg: ArchConfig,
    name: str,
    *,
    blocks: int,
    batch: int = 4,
    seq: int = 64,
    max_residency: int = 4,
    arrival: float = 0.0,
    seed: int = 0,
    opt_cfg: adamw.OptConfig = adamw.OptConfig(lr=1e-3, warmup_steps=5,
                                               total_steps=1000),
    checkpointer: Optional[Checkpointer] = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    tenant: Optional[str] = None,
) -> ExecutorJob:
    """A training job: ``blocks`` microbatch steps of a reduced model.

    Blocks mutate the job's (params, opt_state) held in a closure; because
    preemption happens only at block boundaries, the state is always
    consistent — checkpoint (if configured) and hand-off need no extra
    coordination.
    """
    key = jax.random.PRNGKey(seed)
    state = {"params": lm.init(cfg, key),
             "opt": None, "block": 0}
    state["opt"] = adamw.init(state["params"])
    if resume and checkpointer is not None and checkpointer.latest_step() is not None:
        step, restored, _ = checkpointer.restore(
            {"params": state["params"], "opt": state["opt"]})
        state["params"], state["opt"] = restored["params"], restored["opt"]
        state["block"] = step

    @jax.jit
    def train_step(params, opt_state, tokens):
        def loss(p):
            return lm.loss_fn(cfg, p, {"tokens": tokens})[0]
        loss_val, grads = jax.value_and_grad(loss)(params)
        new_p, new_s, _ = adamw.update(grads, opt_state, params, opt_cfg)
        return new_p, new_s, loss_val

    data_key = jax.random.PRNGKey(seed + 1)

    def warmup():
        tokens = jax.random.randint(jax.random.fold_in(data_key, 0),
                                    (batch, seq), 0, cfg.vocab_size)
        out = train_step(state["params"], state["opt"], tokens)
        jax.block_until_ready(out[2])   # compile only; discard results

    def make_block_fn(residency: int) -> Callable[[], None]:
        def block():
            i = state["block"]
            tokens = jax.random.randint(
                jax.random.fold_in(data_key, i), (batch, seq), 0,
                cfg.vocab_size)
            p, o, loss_val = train_step(state["params"], state["opt"], tokens)
            jax.block_until_ready(loss_val)
            state["params"], state["opt"] = p, o
            state["block"] = i + 1
            if (checkpointer is not None and checkpoint_every
                    and (i + 1) % checkpoint_every == 0):
                checkpointer.save(i + 1, {"params": p, "opt": o},
                                  {"job": name})
        return block

    return ExecutorJob(name=name, num_blocks=blocks - state["block"],
                       max_residency=max_residency,
                       make_block_fn=make_block_fn, arrival=arrival,
                       warmup_fn=warmup, tenant=tenant)


# The leaves that a served step reads only through ``cast(leaf, compute
# dtype)``: the matrix-product weights of linears, MLPs and attention, the
# head and the embedding table; the latent attention's projections; the
# experts and their router.
_COMPUTE_DTYPE_LEAVES = frozenset({
    "w", "wq", "wk", "wv", "wo", "table",
    "wdq", "wuq", "wdkv", "wkr", "wuk", "wuv",
    "gate_w", "up_w", "down_w", "router"})


def _served_leaf(path, leaf: jnp.ndarray) -> jnp.ndarray:
    if path[-1].key in _COMPUTE_DTYPE_LEAVES:
        return cast(leaf, DEFAULT_COMPUTE_DTYPE)
    return leaf


def serve_weights(cfg: ArchConfig, key) -> Dict:
    """The float32 set of :func:`lm.init`, with each matrix-product weight
    (``w`` of linears and MLPs, ``wq``/``wk``/``wv``/``wo``, ``lm_head.w``,
    the latent attention's ``wdq``/``wuq``/``wdkv``/``wkr``/``wuk``/``wuv``,
    the experts' ``gate_w``/``up_w``/``down_w`` and their ``router``) and
    the embedding table held in the compute dtype (bfloat16).

    The served steps read these leaves only through ``cast(leaf, compute
    dtype)``, so rounding them once here feeds every matrix product the
    same bits as rounding them on every step, and a step converts no
    weight.  Every other leaf stays float32 as drawn: the norm scales and
    the recurrent mixers' decay and step parameters, which the steps read
    in float32, and the biases and the recurrent mixers' own projections,
    which they cast at use as before.  Training keeps its float32 masters
    (:func:`make_train_job`).
    """
    return jax.tree_util.tree_map_with_path(_served_leaf, lm.init(cfg, key))


@functools.lru_cache(maxsize=None)
def serve_params(cfg: ArchConfig, weight_seed: int) -> Dict:
    """The weight set that every serve job of ``cfg`` shares:
    :func:`serve_weights` drawn from ``weight_seed``, its matrix weights
    and embedding table in bfloat16, every other leaf in float32.  The
    steps cast those leaves to bfloat16 at use anyway, so they compute the
    same values as from the float32 set, without a cast on every step.

    The draw and the cast are one program, so no whole float32 set is ever
    held on the device.  Cached for the life of the process: a server holds
    one copy of each model it serves, however many jobs (and KV caches) it
    runs on it.
    """
    return jax.jit(functools.partial(serve_weights, cfg))(
        jax.random.PRNGKey(weight_seed))


# Module-level jits keyed by the static configuration and cache length:
# every job of one (configuration, batch, prompt length, cache length)
# reuses one compiled program instead of tracing its own closure.
@functools.partial(jax.jit, static_argnums=0, static_argnames="max_seq")
def _prefill(cfg: ArchConfig, params, tokens, *, max_seq: int):
    return lm.prefill(cfg, params, tokens, max_seq=max_seq)


@functools.partial(jax.jit, static_argnums=0)
def _decode(cfg: ArchConfig, params, token, caches, lengths):
    logits, caches = lm.decode_step(cfg, params, token, caches, lengths)
    return jnp.argmax(logits, -1), caches


@dataclass
class ServeJob(ExecutorJob):
    """A serving job; its blocks append each generated ``[batch]`` token
    array, still on the device, to ``generated``."""

    params: Optional[Dict] = None
    generated: List = field(default_factory=list)

    def tokens(self) -> np.ndarray:
        """``[batch, n_generated]`` token ids, fetched from the device in
        one transfer."""
        return np.asarray(jnp.stack(self.generated, axis=1))


def make_serve_job(
    cfg: ArchConfig,
    name: str,
    *,
    blocks: int,
    tokens_per_block: int = 8,
    batch: int = 2,
    prompt_len: int = 16,
    max_residency: int = 4,
    arrival: float = 0.0,
    seed: int = 0,
    weight_seed: int = 0,
    tenant: Optional[str] = None,
) -> ServeJob:
    """A serving job: ``blocks`` decode chunks of ``tokens_per_block`` each
    against a live KV cache (prefill happens in the first block).

    ``seed`` draws the prompt; ``weight_seed`` picks the shared weight set
    (:func:`serve_params`).
    """
    params = serve_params(cfg, weight_seed)
    max_seq = prompt_len + blocks * tokens_per_block + 8
    prompt = jax.random.randint(jax.random.PRNGKey(seed),
                                (batch, prompt_len), 0, cfg.vocab_size)
    state: Dict = {"caches": None, "lengths": None, "token": None}
    generated: List = []

    def warmup():
        logits, caches = _prefill(cfg, params, prompt, max_seq=max_seq)
        tok = jnp.argmax(logits, -1)
        lengths = jnp.full((batch,), prompt_len, jnp.int32)
        out = _decode(cfg, params, tok, caches, lengths)
        jax.block_until_ready(out[0])   # compile only; discard results

    def make_block_fn(residency: int) -> Callable[[], None]:
        def block():
            if state["caches"] is None:
                with tracing.span("job.prefill"):
                    logits, caches = _prefill(cfg, params, prompt,
                                              max_seq=max_seq)
                    state["caches"] = caches
                    if tracing.enabled():
                        tracing.count("job.cache_bytes", sum(
                            leaf.nbytes for leaf in jax.tree.leaves(caches)),
                            job=name)
                    state["lengths"] = jnp.full((batch,), prompt_len,
                                                jnp.int32)
                    state["token"] = jnp.argmax(logits, -1)
                    generated.append(state["token"])
            with tracing.span("job.dispatch"):
                for _ in range(tokens_per_block):
                    tok, caches = _decode(cfg, params, state["token"],
                                          state["caches"], state["lengths"])
                    state["token"] = tok
                    state["caches"] = caches
                    state["lengths"] = state["lengths"] + 1
                    generated.append(tok)
            with tracing.span("job.sync"):
                jax.block_until_ready(state["token"])
        return block

    return ServeJob(name=name, num_blocks=blocks,
                    max_residency=max_residency,
                    make_block_fn=make_block_fn, arrival=arrival,
                    warmup_fn=warmup, tenant=tenant, params=params,
                    generated=generated)
