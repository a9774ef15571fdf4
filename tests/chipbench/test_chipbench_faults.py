"""The output check fails a run whose timed path is broken underneath.

Each fault is planted in the program's decode step, which every block of
the window runs, and the rest of the run goes on as usual; the check must
then read ``correct`` false. A one-chip serving cell can have three of the
contract's faults: a step that returns its state unchanged, half of the
batch left out, and a token altered where it is produced. (It has no
exchange between chips to leave out.)
"""

import pytest
from conftest import TINY
from tinybench import run_tiny

from repro.core import jobs


def _state_unchanged(decode):
    def step(cfg, params, token, caches, lengths):
        tok, _ = decode(cfg, params, token, caches, lengths)
        return tok, caches
    return step


def _half_batch(decode):
    def step(cfg, params, token, caches, lengths):
        tok, caches = decode(cfg, params, token, caches, lengths)
        half = tok.shape[0] // 2
        return tok.at[half:].set(tok[:half]), caches
    return step


def _token_altered(decode):
    def step(cfg, params, token, caches, lengths):
        tok, caches = decode(cfg, params, token, caches, lengths)
        return tok.at[0].set((tok[0] + 1) % TINY["vocab_size"]), caches
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_a_broken_decode_step_is_not_correct(bench_copy, monkeypatch, fault):
    monkeypatch.setattr(jobs, "_decode", fault(jobs._decode))
    out = run_tiny(bench_copy, "tiny.closed2", seconds=1.0)
    gap = out.line["checks"]["widest_logit_gap"]
    assert out.line["correct"] is False
    assert gap["value"] > 3 * gap["limit"]
