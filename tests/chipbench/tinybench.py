"""Runs a tiny cell of a benchmark copy (``conftest.bench_copy``) on the
CPU."""

from __future__ import annotations

from pathlib import Path


def run_tiny(root: Path, cell: str, *, seed: int = 7, seconds: float = 1.5,
             control: bool = False):
    """One run of a tiny cell of ``root`` on the CPU, with no chip check and
    no persistent compile cache; returns the harness's ``Outcome``."""
    import time

    import jax

    from chipbench import catalog
    from chipbench.harness import run_cell

    c = catalog.find_cell(cell, root=root, bench_dir=root / "chipbench")
    return run_cell(c, seed=seed, seconds=seconds, trace=False,
                    device=jax.devices()[0], t_process=time.perf_counter(),
                    control=control, compile_cache=False)
