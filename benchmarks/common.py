"""Benchmark plumbing: timing + CSV row helpers."""
from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, List, Tuple

Row = Tuple[str, float, str]   # (name, us_per_call, derived)


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median wall-time per call in microseconds (blocks on jax outputs)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def print_rows(rows: List[Row]) -> None:
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


@contextlib.contextmanager
def pallas_interpreter_off_tpu():
    """Benches that call Pallas kernels directly run them through the
    Pallas interpreter off the TPU, which has no native path for them;
    kernels never choose the interpreter on their own, so ask for it
    here, only for the bench's duration."""
    import jax
    if jax.default_backend() == "tpu":
        yield
        return
    prev = os.environ.get("REPRO_PALLAS_INTERPRET")
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["REPRO_PALLAS_INTERPRET"]
        else:
            os.environ["REPRO_PALLAS_INTERPRET"] = prev
