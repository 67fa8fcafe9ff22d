"""Audit runners: glue between the pass registry and the call sites.

Two entry points:

* :func:`run_program_checks` — run every registered program pass against
  one :class:`ProgramArtifacts` bundle (whatever subset of jaxpr /
  StableHLO / HLO the caller could produce; passes missing their inputs
  skip silently).
* :func:`make_cache_lint` — build the hook :class:`repro.runtime
  .compile_cache.CompileCache` calls on every **cold** compile. The hook
  extracts HLO text from the built executable (duck-typed ``as_text``),
  merges any artifacts the build closure stashed (train/serve stash the
  StableHLO text of the ``Lowered`` stage — free, no extra trace), runs
  the program passes, logs findings, and raises :class:`LintError` in
  ``error`` mode so a hazardous program never enters the cache or the
  persistent store.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from .registry import available_passes
from .report import LINT_MODES, SEV_ERROR, LintReport

# registration side effects: importing the check modules populates the
# registry exactly once (registry.register_pass rejects duplicates)
from . import plan_checks    # noqa: F401  (plan passes)
from . import jaxpr_checks   # noqa: F401  (program passes, jaxpr tier)
from . import hlo_checks     # noqa: F401  (program passes, text tier)

__all__ = ["ProgramArtifacts", "run_program_checks", "make_cache_lint"]


@dataclass
class ProgramArtifacts:
    """Whatever one cold compile could surface for auditing."""

    key: Any = None                 # bucket key (subject line only)
    jaxpr: Any = None               # ClosedJaxpr (offline CLI / tests)
    stablehlo: Optional[str] = None  # Lowered.as_text()
    hlo: Optional[str] = None        # Compiled.as_text()
    platform: str = "cpu"            # jax.default_backend() at the site
    latency_hiding: bool = False     # launch/mesh.latency_hiding_active
    const_threshold: int = 1 << 16   # program-baked-constant elements

    def available(self) -> Dict[str, bool]:
        return {"jaxpr": self.jaxpr is not None,
                "stablehlo": bool(self.stablehlo),
                "hlo": bool(self.hlo)}


def run_program_checks(artifacts: ProgramArtifacts) -> LintReport:
    """Run every program pass whose inputs are available."""
    have = artifacts.available()
    report = LintReport(
        subject=repr(artifacts.key) if artifacts.key is not None else "")
    for p in available_passes("program"):
        if p.needs and not any(have.get(n) for n in p.needs):
            continue
        report.ran(p.name)
        try:
            p.fn(artifacts, report)
        except Exception as e:  # noqa: BLE001 - a crashed pass is a finding
            report.add(p.name, SEV_ERROR,
                       f"pass crashed: {type(e).__name__}: {e}")
    return report


def make_cache_lint(mode: str, *, log: Optional[Callable[[str], None]] = None,
                    platform: Optional[str] = None,
                    latency_hiding: bool = False,
                    stash: Optional[Dict[str, Any]] = None) -> Optional[Callable]:
    """The ``CompileCache(lint=...)`` hook for one launch site.

    ``stash`` is a mutable dict the site's build closure may fill with
    richer artifacts (``"stablehlo"``, ``"jaxpr"``) during the cold
    build; the hook pops them so one build's artifacts never leak into
    the next bucket's audit. Returns None for mode ``"off"`` so the
    cache skips the hook entirely.
    """
    if mode not in LINT_MODES:
        raise ValueError(f"lint mode must be one of {LINT_MODES}, "
                         f"got {mode!r}")
    if mode == "off":
        return None
    if platform is None:
        try:
            import jax
            platform = jax.default_backend()
        except Exception:  # noqa: BLE001 - no runtime yet: stay generic
            platform = "cpu"

    def hook(key, value) -> LintReport:
        art = ProgramArtifacts(key=key, platform=platform,
                               latency_hiding=latency_hiding)
        if stash is not None:
            art.stablehlo = stash.pop("stablehlo", None)
            art.jaxpr = stash.pop("jaxpr", None)
        as_text = getattr(value, "as_text", None)
        if callable(as_text):
            try:
                art.hlo = as_text()
            except Exception:  # noqa: BLE001 - text is best-effort
                art.hlo = None
        report = run_program_checks(art)
        if log is not None and report.findings:
            for f in report.findings:
                log(f"[lint] {f}")
        if mode == "error":
            report.raise_if_findings()
        return report

    return hook
