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


def test_serve_jobs_share_weights_and_compiled_steps():
    """Three jobs of one configuration hold one weight pytree, and only the
    first compiles: the others reuse its prefill and decode programs."""
    cfg = get_arch("yi-6b").reduced()
    compiles = []

    def listen(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def job(seed):
        return make_serve_job(cfg, "yi-6b", blocks=2, tokens_per_block=2,
                              batch=2, prompt_len=8, seed=seed,
                              weight_seed=7)

    first = job(0)
    LaneExecutor([first], make_policy("fifo"), n_lanes=1).run()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        others = [job(1), job(2)]
        LaneExecutor(others, make_policy("fifo"), n_lanes=1).run()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert others[0].params is first.params
    assert others[1].params is first.params
    assert compiles == []
    tokens = [j.tokens() for j in [first] + others]
    assert all(t.shape == (2, 1 + 2 * 2) for t in tokens)
    # one weight set, different prompts: the jobs' outputs differ
    assert not np.array_equal(tokens[0], tokens[1])


def test_serve_weight_seed_is_split_from_the_prompt_seed():
    cfg = get_arch("yi-6b").reduced()
    a = make_serve_job(cfg, "a", blocks=1, seed=0, weight_seed=3)
    b = make_serve_job(cfg, "b", blocks=1, seed=5, weight_seed=3)
    c = make_serve_job(cfg, "c", blocks=1, seed=0, weight_seed=4)
    assert a.params is b.params
    leaf = jax.tree.leaves(a.params)[0]
    assert not jnp.array_equal(leaf, jax.tree.leaves(c.params)[0])
