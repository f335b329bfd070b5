"""Runtime tracing contracts shared by the engines and the tier-1 tests.

Three guards, each the mechanical form of an invariant this repo used to
pin by hand:

* `CompileCounter` / `compile_guard` — trace-time compile counting.  The
  engine's 1-prefill/1-decode/1-draft/1-verify contract and the
  bs-warmup one-compile-per-stage contract were previously four separate
  hand-rolled closures; they now share one counter type and one guard.
* `transfer_guard` — a thin wrapper over ``jax.transfer_guard`` for the
  hot loops.  NOTE: jax's transfer guards are enforced on TPU/GPU
  backends but are a no-op on the CPU backend (CPU "transfers" are
  zero-copy), so on CPU CI this wrapper is best-effort: it still
  exercises the code path and catches API misuse, while on real
  hardware it turns any unannounced device→host sync into an error.
* `donation_check` — verifies donated buffers really were consumed
  (``is_deleted()``) after a donating call, catching silently-dropped
  ``donate_argnums`` (e.g. an aliasing mismatch downgraded to a copy).

Debug-mode wiring: `Trainer` and `OnlineEngine` enable `transfer_guard`
around their per-step loops when constructed with ``debug_guards=True``
(default comes from the ``REPRO_DEBUG_GUARDS`` env var), which is how
the engine-parity CI leg runs.
"""
from __future__ import annotations

import contextlib
import os
from typing import Callable, Dict, Mapping, Optional, Union

import jax


class CompileGuardError(AssertionError):
    """A compile_guard limit was violated (unexpected retrace)."""


class DonationError(AssertionError):
    """A donated buffer was not consumed by the donating call."""


def env_debug_guards(default: bool = False) -> bool:
    """Default for the engines' ``debug_guards`` flag: the
    ``REPRO_DEBUG_GUARDS`` env var ("1"/"true"/"yes" enable)."""
    raw = os.environ.get("REPRO_DEBUG_GUARDS")
    if raw is None:
        return default
    return raw.strip().lower() in ("1", "true", "yes", "on")


class CompileCounter:
    """Counts XLA traces per label.

    ``counter.jit(label, fn, **jit_kwargs)`` wraps ``fn`` so the counter
    increments at *trace* time — i.e. exactly once per compilation for
    fixed shapes — then applies ``jax.jit``.  This is the same
    trace-time-closure trick the engines used ad hoc; centralizing it
    means `compile_guard` can assert on any subset of labels.
    """

    def __init__(self):
        self.counts: Dict[str, int] = {}

    def bump(self, label: str) -> None:
        """Record one trace for `label` (for callers that already have a
        traced function and just want the bookkeeping)."""
        self.counts[label] = self.counts.get(label, 0) + 1

    def jit(self, label: str, fn: Callable, **jit_kwargs) -> Callable:
        self.counts.setdefault(label, 0)

        def traced(*args, **kwargs):
            self.bump(label)  # runs at trace time, not per call
            return fn(*args, **kwargs)

        # the program takes the label's name: compile logs, compile-time
        # events and profiler traces then say which step they are
        traced.__name__ = traced.__qualname__ = label
        return jax.jit(traced, **jit_kwargs)

    def __getitem__(self, label: str) -> int:
        return self.counts.get(label, 0)

    def total(self) -> int:
        return sum(self.counts.values())

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)

    def __repr__(self) -> str:
        return f"CompileCounter({self.counts!r})"


@contextlib.contextmanager
def compile_guard(limit: Union[int, Mapping[str, int]],
                  counter: Optional[CompileCounter] = None,
                  *, exact: bool = False):
    """Assert at most (or with ``exact=True``, exactly) N new traces
    happen inside the block.

    ``limit`` is either a total across all labels (int) or a per-label
    mapping; labels absent from the mapping are unconstrained.  Yields
    the counter so call sites can create one inline::

        with compile_guard({"decode": 1}, eng.compiles, exact=True):
            for _ in range(64):
                eng.tick()
    """
    counter = counter if counter is not None else CompileCounter()
    before = counter.snapshot()
    yield counter
    after = counter.snapshot()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in set(before) | set(after)}
    if isinstance(limit, Mapping):
        for label, lim in limit.items():
            got = delta.get(label, 0)
            bad = got != lim if exact else got > lim
            if bad:
                op = "==" if exact else "<="
                raise CompileGuardError(
                    f"compile_guard: expected {op}{lim} new traces for "
                    f"{label!r}, got {got} (delta={delta})")
    else:
        got = sum(delta.values())
        bad = got != limit if exact else got > limit
        if bad:
            op = "==" if exact else "<="
            raise CompileGuardError(
                f"compile_guard: expected {op}{limit} new traces total, "
                f"got {got} (delta={delta})")


@contextlib.contextmanager
def transfer_guard(level: str = "disallow"):
    """Disallow implicit device→host transfers inside the block.

    Levels are jax's: "allow", "log", "disallow", "disallow_explicit".
    Enforced on TPU/GPU; the CPU backend never fires transfer guards
    (host and device memory are the same), so this is a structural no-op
    there — kept active anyway so the same test code is load-bearing the
    moment it runs on real hardware.
    """
    with jax.transfer_guard_device_to_host(level):
        yield


def donation_check(fn: Callable, donate_argnums, *args, **kwargs):
    """Call ``fn(*args, **kwargs)`` and verify every jax-array leaf of
    the arguments at ``donate_argnums`` positions was consumed
    (``is_deleted()``).  Returns ``fn``'s result.

    Use on a handle jitted with the same ``donate_argnums``: if XLA
    silently downgraded donation to a copy (aliasing/layout mismatch) or
    the wrapper dropped the donate flags, this raises `DonationError`
    instead of letting the train step double its parameter memory.
    """
    if isinstance(donate_argnums, int):
        donate_argnums = (donate_argnums,)
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    for i in donate_argnums:
        if i >= len(args):
            continue
        for leaf in jax.tree.leaves(args[i]):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                raise DonationError(
                    f"donation_check: argument #{i} has a live leaf "
                    f"(shape={leaf.shape}, dtype={leaf.dtype}) after the "
                    f"donating call — donation was dropped (aliasing "
                    f"mismatch or missing donate_argnums on the jit)")
    return out
