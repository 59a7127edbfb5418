"""Runtime sanitizer harness for the engine family.

Complements the static pass with two dynamic checks:

* :func:`strict_mode` — a context manager that arms
  ``jax.transfer_guard("disallow")`` (no implicit host<->device
  transfers: the PR 6 "zero per-round host transfers" contract) and
  optionally ``jax_debug_nans``. Engine *setup* phases (population
  construction, data partitioning) legitimately move host data onto the
  device; they declare that with :func:`setup_transfers`, which opens a
  scoped ``transfer_guard("allow")`` window inside strict mode.

* :func:`retrace_guard` — captures ``jax.log_compiles`` output and
  asserts each traced computation compiles exactly once per shape. A
  second identical "Compiling <name>" record means the engine retraced
  — a shape or static-argument leak that silently multiplies compile
  time and breaks the one-compile-per-config contract.

``jax_debug_nans`` note: fault-injected runs (``FaultConfig`` with
``corrupt_prob > 0``) produce NaN deltas *by design* (the quarantine
masks them out with ``0 * nan`` arithmetic), so strict mode only arms
debug_nans when asked; never combine it with corrupt-fault configs.
"""
from __future__ import annotations

import contextlib
import logging
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional


@contextlib.contextmanager
def strict_mode(*, debug_nans: bool = False) -> Iterator[None]:
    """Run the enclosed engine calls with implicit transfers disallowed.

    Any implicit host->device transfer (a python scalar or numpy array
    flowing into a jitted computation, a stray ``jnp.asarray`` on host
    data) raises instead of silently syncing. Explicit
    ``jax.device_put`` / ``jax.device_get`` remain allowed — the point
    is that every transfer must be *named*, not that none happen.
    """
    import jax

    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.transfer_guard("disallow"))
        if debug_nans:
            stack.enter_context(jax.debug_nans(True))
        yield


@contextlib.contextmanager
def setup_transfers() -> Iterator[None]:
    """Declare a setup phase that may move host data to the device.

    Engine entry points wrap their one-time setup (population build,
    data partition, constant materialization) in this so the steady
    state stays guarded under :func:`strict_mode` while setup is exempt.
    Outside strict mode this is a no-op window with the same semantics.
    """
    import jax

    with jax.transfer_guard("allow"):
        yield


def _compiled_name(msg: str) -> str:
    """The function name out of a "Compiling <name> with global shapes
    and types [...]" record. JAX 0.9 prints the name as ``jit(<name>)``."""
    name = msg[len("Compiling "):].split(" with global shapes", 1)[0]
    if name.startswith("jit(") and name.endswith(")"):
        name = name[len("jit("):-1]
    return name


@dataclass
class CompileLog:
    """Compile events observed by :func:`retrace_guard`.

    ``watch`` scopes retrace detection to the named computations (the
    engine entry points: ``run``, ``evaluate``, …). jax-internal eager
    helpers (``broadcast_in_dim``, ``_normal``, …) legitimately compile
    many times under one message — their differing *static* arguments
    are not part of the log line — so unscoped detection would cry wolf
    on any nontrivial setup phase. ``watch=None`` watches everything."""

    records: List[str] = field(default_factory=list)
    watch: Optional[frozenset] = None

    def _relevant(self) -> List[str]:
        if self.watch is None:
            return self.records
        return [r for r in self.records
                if _compiled_name(r) in self.watch]

    def counts(self) -> Dict[str, int]:
        """Full-message -> times compiled, for watched computations. A
        count > 1 for the *same* message means an identical computation
        was traced twice."""
        out: Dict[str, int] = {}
        for r in self._relevant():
            out[r] = out.get(r, 0) + 1
        return out

    def compiles_of(self, name: str) -> int:
        """Total compiles of the computation named ``name``."""
        return sum(1 for r in self.records if _compiled_name(r) == name)

    def retraced(self) -> Dict[str, int]:
        return {msg: n for msg, n in self.counts().items() if n > 1}

    def assert_no_retrace(self) -> None:
        dup = self.retraced()
        if dup:
            detail = "\n".join(f"  x{n}: {msg}" for msg, n in dup.items())
            raise AssertionError(
                f"retrace detected — identical computation compiled more "
                f"than once:\n{detail}")

    def assert_compiled_once(self, *names: str) -> None:
        """Each ``name`` appears in >=1 compile record and no record
        mentioning it repeats."""
        self.assert_no_retrace()
        for name in names:
            if self.compiles_of(name) < 1:
                raise AssertionError(
                    f"expected a compile of '{name}' but none was "
                    f"observed; saw: {self.records}")


class _CompileHandler(logging.Handler):
    """Captures the "Compiling <name> with global shapes and types
    [...]" records ``jax.log_compiles`` emits (at WARNING) — one per
    actual XLA compile, with the name + abstract shapes identifying the
    computation, so a repeated identical message IS a retrace."""

    def __init__(self, log: CompileLog):
        super().__init__(level=logging.INFO)
        self.log = log

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.log.records.append(msg.strip())


@contextlib.contextmanager
def retrace_guard(watch: Optional[Iterable[str]] = None,
                  ) -> Iterator[CompileLog]:
    """Record every XLA compile inside the block.

    Usage::

        with retrace_guard(watch=("run", "evaluate")) as log:
            run_fl_scanned(cfg)
            run_fl_scanned(cfg)        # cached: no second compile
        log.assert_compiled_once("run")
    """
    import jax

    log = CompileLog(watch=None if watch is None else frozenset(watch))
    handler = _CompileHandler(log)
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles(True):
            yield log
    finally:
        logger.removeHandler(handler)
