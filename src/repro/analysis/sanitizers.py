"""Runtime sanitizers: recompile accounting and numeric checks.

Static lint cannot see *dynamic* hazards — a gate tensor whose shape
changes between serving rounds silently recompiles every jitted policy
each round.  These helpers make those hazards loud:

* `recompile_guard` — context manager that counts XLA compilations per
  jitted-function name while active, and (optionally) asserts an exact
  expected count on exit.  Used by ``tests/test_recompile_gate.py`` to
  pin ``des_select_batch`` / ``channel_aware_mask`` / the siftmoe
  ``route_mask`` to exactly one compile across a multi-round
  `ServingFrontend` run.
* `debug_nan_guard` — scoped ``jax_debug_nans`` toggle.
* `assert_all_finite` — finiteness check policies opt into via
  ``ScheduleContext(debug_checks=True)``; numpy-side on concrete
  values, `checkify.check` on tracers (pair with `checked`).
* `checked` — wrap a function with ``checkify`` float/NaN checks and
  re-raise the first error on the host.

Compile counting rides on ``jax_log_compiles``: JAX logs one WARNING
per real cache-missing compilation ("Compiling <name> with global
shapes ...") from its dispatch/pxla loggers; cache hits log nothing.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import re
from typing import Dict, Iterator, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: JAX >= 0.5 names the jitted function as ``jit(<name>)``.
_COMPILE_RE = re.compile(
    r"Compiling (?:jit\()?([A-Za-z0-9_<>.\-]+)\)? (?:with|for)")

#: Loggers that emit the per-compilation record (version-dependent).
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")


class RecompileError(AssertionError):
    """Raised by `recompile_guard` when counts deviate from `expect`."""


class CompileLog(logging.Handler):
    """Collects per-function compile counts while attached."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.counts: Dict[str, int] = {}

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            name = m.group(1)
            self.counts[name] = self.counts.get(name, 0) + 1

    def count(self, name: str) -> int:
        """Compilations of functions whose jit name contains `name`
        (jit wrappers decorate the raw ``__name__``)."""
        return sum(v for k, v in self.counts.items() if name in k)

    def assert_counts(self, expect: Mapping[str, int]) -> None:
        errors = []
        for name, want in expect.items():
            got = self.count(name)
            if got != want:
                errors.append(f"{name}: expected {want} compile(s), "
                              f"observed {got}")
        if errors:
            raise RecompileError(
                "; ".join(errors)
                + f" (all compiles: {dict(sorted(self.counts.items()))})")


@contextlib.contextmanager
def recompile_guard(expect: Optional[Mapping[str, int]] = None
                    ) -> Iterator[CompileLog]:
    """Count jit compilations in the `with` body.

    ``expect`` maps jit-function-name substrings to exact expected
    compile counts, asserted on (successful) exit; functions not named
    in ``expect`` are ignored, so ambient eager-op compiles
    (``convert_element_type`` etc.) don't trip the guard.  Yields the
    `CompileLog` for ad-hoc queries either way.
    """
    log = CompileLog()
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    loggers = [logging.getLogger(n) for n in _COMPILE_LOGGERS]
    prev_levels = [lg.level for lg in loggers]
    for lg in loggers:
        lg.addHandler(log)
        if lg.level > logging.WARNING:
            lg.setLevel(logging.WARNING)
    try:
        yield log
        if expect is not None:
            log.assert_counts(expect)
    finally:
        for lg, lvl in zip(loggers, prev_levels):
            lg.removeHandler(log)
            lg.setLevel(lvl)
        jax.config.update("jax_log_compiles", prev)


@contextlib.contextmanager
def debug_nan_guard() -> Iterator[None]:
    """Scoped ``jax_debug_nans``: any NaN produced by a jitted function
    inside the body raises immediately with a de-optimized re-run."""
    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)


def assert_all_finite(value, name: str = "value") -> None:
    """Raise `FloatingPointError` if any float leaf holds NaN/Inf.

    On concrete arrays (the scheduler-policy host path) this is a plain
    numpy check.  On tracers it emits a `checkify.check`, so in-graph
    callers must be wrapped with `checked` (or ``checkify.checkify``)
    for the check to be functionalized.
    """
    from jax.experimental import checkify

    for i, leaf in enumerate(jax.tree_util.tree_leaves(value)):
        if isinstance(leaf, jax.core.Tracer):
            checkify.check(jnp.all(jnp.isfinite(leaf)),
                           f"non-finite values in {name} (leaf {i})")
            continue
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) and \
                not np.isfinite(arr).all():
            bad = int(np.size(arr) - np.isfinite(arr).sum())
            raise FloatingPointError(
                f"{bad} non-finite value(s) in {name} (leaf {i}, "
                f"shape {arr.shape})")


def checked(fn):
    """Wrap `fn` with checkify float/NaN/user checks; errors raise on
    the host after the call returns."""
    from jax.experimental import checkify

    errors = checkify.float_checks | checkify.user_checks
    cfn = checkify.checkify(fn, errors=errors)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        err, out = cfn(*args, **kwargs)
        checkify.check_error(err)
        return out

    return wrapper
