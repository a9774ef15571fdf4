"""The served path of chip_smoke.py on the CPU, at reduced widths.

``chip_smoke.py`` itself refuses to run anywhere but on a TPU; these tests
drive its body through the same functions at the ``reduced()`` widths, and
check what serve jobs of one configuration share.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import jobs, tracing
from repro.core.executor import LaneExecutor
from repro.core.jobs import make_serve_job
from repro.core.policies import make_policy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_smoke_refuses_the_cpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_body_preempts_and_keeps_tokens_at_reduced_widths(chip_smoke):
    """One long job and two late short ones under SRTF on one lane: the
    short jobs finish first, nothing compiles inside the window, and every
    job's tokens equal its solo run's."""
    args = chip_smoke.serve_args(layers=0, long_blocks=32, short_blocks=4,
                                 batch=2, prompt_len=16, tokens_per_block=2)
    out = chip_smoke.run_served(args)
    assert out["order"][-1] == "yi-6b#0"
    assert out["window_compiles"] == 0
    assert out["metrics"].stp > 0.0


class _Compiles:
    """Counts the programs readied (compiled or loaded) inside ``with``."""

    def __init__(self):
        self.count = 0

    def _listen(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


def _yi_job(seed, blocks=2):
    return make_serve_job(get_arch("yi-6b").reduced(), f"yi-{blocks}",
                          blocks=blocks, tokens_per_block=2, batch=2,
                          prompt_len=8, seed=seed, weight_seed=7)


def test_serve_jobs_share_weights_and_compiled_steps():
    """Three jobs of one configuration hold one weight pytree, and only the
    first compiles: the others reuse its prefill and decode programs."""
    first = _yi_job(0)
    LaneExecutor([first], make_policy("fifo"), n_lanes=1).run()
    with _Compiles() as compiles:
        others = [_yi_job(1), _yi_job(2)]
        LaneExecutor(others, make_policy("fifo"), n_lanes=1).run()
    assert others[0].params is first.params
    assert others[1].params is first.params
    assert compiles.count == 0
    tokens = [j.tokens() for j in [first] + others]
    assert all(t.shape == (2, 1 + 2 * 2) for t in tokens)
    # one weight set, different prompts: the jobs' outputs differ
    assert not np.array_equal(tokens[0], tokens[1])


@pytest.fixture
def cold(monkeypatch):
    """No program key warmed yet in this process, and the warm-up's calls
    of ``_prefill`` and ``_decode`` counted."""
    monkeypatch.setattr(jobs, "_warmed", set())
    calls = {"prefill": 0, "decode": 0}

    def counted(name, fn):
        def call(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return call

    monkeypatch.setattr(jobs, "_prefill", counted("prefill", jobs._prefill))
    monkeypatch.setattr(jobs, "_decode", counted("decode", jobs._decode))
    return calls


@pytest.mark.parametrize("blocks, calls", [(2, 0), (3, 1)],
                         ids=["same_key", "new_cache_length"])
def test_serve_warmup_runs_the_programs_once_per_program_key(
        cold, blocks, calls):
    """After one job of a key has been served, a job of that key warms
    without calling the prefill or the decode and readies no program; a
    job with another cache length still runs both. Either way its first
    block then readies no program."""
    LaneExecutor([_yi_job(0)], make_policy("fifo"), n_lanes=1).run()
    cold.update(prefill=0, decode=0)
    job = _yi_job(1, blocks)
    with _Compiles() as warm:
        job.warmup_fn()
    assert cold == {"prefill": calls, "decode": calls}
    if not calls:
        assert warm.count == 0
    with _Compiles() as first_block:
        job.make_block_fn(1)()
    assert first_block.count == 0
    assert len(job.generated) == 1 + 2


def test_serve_job_after_a_skipped_warmup_gives_the_same_tokens(cold):
    """The job whose warm-up ran and the same seed's job whose warm-up
    returned at once generate the same tokens."""
    tokens = []
    for expected_prefills in (1, 0):
        cold.update(prefill=0)
        job = _yi_job(5)
        job.warmup_fn()
        assert cold["prefill"] == expected_prefills
        LaneExecutor([job], make_policy("fifo"), n_lanes=1).run()
        tokens.append(job.tokens())
    np.testing.assert_array_equal(tokens[0], tokens[1])


def test_serve_warmup_counts_whether_it_ran(cold):
    """``job.warmup_ran`` records 1 for the key's first warm-up, then 0."""
    tracing.start()
    try:
        for seed in (0, 1):
            _yi_job(seed).warmup_fn()
    finally:
        records = tracing.stop()
    ran = [r for r in records if r.name == "job.warmup_ran"]
    assert [r.args["value"] for r in ran] == [1, 0]
    assert {r.args["job"] for r in ran} == {"yi-2"}
    assert {r.args["max_seq"] for r in ran} == {8 + 2 * 2 + 8}


def test_serve_warmup_that_raises_leaves_its_key_cold(cold, monkeypatch):
    """A warm-up whose decode raises marks nothing warm: the next job of
    the key runs the prefill and the decode again."""
    decode = jobs._decode

    def broken(*args, **kwargs):
        raise RuntimeError("decode failed")

    monkeypatch.setattr(jobs, "_decode", broken)
    with pytest.raises(RuntimeError, match="decode failed"):
        _yi_job(0).warmup_fn()
    monkeypatch.setattr(jobs, "_decode", decode)
    cold.update(prefill=0, decode=0)
    _yi_job(1).warmup_fn()
    assert cold == {"prefill": 1, "decode": 1}


def test_serve_weight_seed_is_split_from_the_prompt_seed():
    cfg = get_arch("yi-6b").reduced()
    a = make_serve_job(cfg, "a", blocks=1, seed=0, weight_seed=3)
    b = make_serve_job(cfg, "b", blocks=1, seed=5, weight_seed=3)
    c = make_serve_job(cfg, "c", blocks=1, seed=0, weight_seed=4)
    assert a.params is b.params
    leaf = jax.tree.leaves(a.params)[0]
    assert not jnp.array_equal(leaf, jax.tree.leaves(c.params)[0])
