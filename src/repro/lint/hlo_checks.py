"""HLO/StableHLO text lint passes: hazards visible in the lowered and
compiled program text.

The compile-path hook runs these on every cold compile — a
``jax.stages.Compiled`` exposes post-optimization HLO via ``as_text()``
and the launch sites stash the pre-compile StableHLO from the
``Lowered`` stage, so no extra tracing happens.

Text anatomy this relies on (jax 0.9 / XLA):

* StableHLO marks donated arguments with ``jax.buffer_donor = true`` on
  ``@main``'s parameters (arguments that could also be established as
  aliases at lowering time appear as ``tf.aliasing_output = N``).
* Compiled HLO records realized donation in the module header:
  ``input_output_alias={ {out}: (in, {}, may-alias), ... }``.
* Async collectives appear as ``-start``/``-done`` op pairs
  (``collective-permute-start`` etc.); a bare ``collective-permute(``
  is a blocking issue slot the latency-hiding scheduler cannot overlap.
"""

from __future__ import annotations

import re
from typing import Dict, List, Set, Tuple

from .registry import register_pass
from .report import SEV_WARNING, LintReport

__all__ = ["stablehlo_donors", "hlo_alias_map", "stablehlo_main_types"]

_ARG_RE = re.compile(r"%arg(\d+):((?:[^%])*)", re.S)
# output index is empty for a non-tuple (single-output) program:
# "input_output_alias={ {}: (0, {}, may-alias) }"
_ALIAS_PAIR_RE = re.compile(r"\{(\d*)\}:\s*\((\d+),\s*\{\}")
_TENSOR_RE = re.compile(r"tensor<([^>]*)>")


def _balanced(text: str, start: int) -> int:
    """Index of the ``)`` closing the ``(`` at ``start``."""
    depth = 0
    for k in range(start, len(text)):
        if text[k] == "(":
            depth += 1
        elif text[k] == ")":
            depth -= 1
            if depth == 0:
                return k
    return len(text)


def _main_signature(stablehlo: str) -> Tuple[str, str]:
    """``(args_blob, results_blob)`` of ``@main``. A lazy one-regex parse
    truncates at the first ``{jax.result_info = ...}`` attribute brace,
    so the argument and result lists are carved out by balanced parens."""
    i = stablehlo.find("@main(")
    if i < 0:
        return "", ""
    lparen = i + len("@main")
    rparen = _balanced(stablehlo, lparen)
    args = stablehlo[lparen + 1:rparen]
    m = re.match(r"\s*->\s*", stablehlo[rparen + 1:])
    if not m:
        return args, ""
    rest = stablehlo[rparen + 1 + m.end():]
    if rest.startswith("("):
        return args, rest[1:_balanced(rest, 0)]
    return args, re.split(r"[\s{]", rest, 1)[0]


# collectives the EPP hot loop issues every tick; all-reduce excluded —
# the gradient all-reduce at step end is outside the latency-critical
# tick loop and often legitimately synchronous
_BLOCKING_COLLECTIVES = ("collective-permute", "all-gather")


def stablehlo_donors(stablehlo: str) -> Set[int]:
    """Argument indices of ``@main`` marked as buffer donors."""
    args, _ = _main_signature(stablehlo)
    donors: Set[int] = set()
    for am in _ARG_RE.finditer(args):
        attrs = am.group(2)
        if "jax.buffer_donor" in attrs or "tf.aliasing_output" in attrs:
            donors.add(int(am.group(1)))
    return donors


def hlo_alias_map(hlo: str) -> Dict[int, int]:
    """``{input_index: output_index}`` pairs realized by the compiler
    (the ``input_output_alias`` module header)."""
    header_end = hlo.find("\n\n")
    header = hlo[:header_end] if header_end > 0 else hlo
    if "input_output_alias" not in header:
        return {}
    start = header.index("input_output_alias")
    return {int(i): int(o) if o else 0
            for o, i in _ALIAS_PAIR_RE.findall(header[start:])}


def stablehlo_main_types(stablehlo: str
                         ) -> Tuple[List[str], List[str]]:
    """``(arg_types, result_types)`` of ``@main`` as tensor-type strings
    (e.g. ``"4x8xf32"``)."""
    arg_blob, result_blob = _main_signature(stablehlo)
    args = [tm.group(1) for tm in _TENSOR_RE.finditer(arg_blob)]
    # the args blob contains only one tensor<> per %arg (attributes hold
    # no tensor types), so position == arg index
    results = [tm.group(1) for tm in _TENSOR_RE.finditer(result_blob)]
    return args, results


def _elems(tensor_type: str) -> int:
    n = 1
    for part in tensor_type.split("x")[:-1]:
        try:
            n *= int(part)
        except ValueError:
            return 0  # dynamic dim
    return n


# ---------------------------------------------------------------------------


@register_pass("program-donation", kind="program",
               needs=("stablehlo", "hlo"),
               doc="donated params/opt-state actually alias an output; "
                   "state-shaped inputs are donated at all")
def _donation(ctx, report: LintReport) -> None:
    stablehlo = getattr(ctx, "stablehlo", None)
    hlo = getattr(ctx, "hlo", None)
    if not stablehlo or not hlo:
        return
    donors = stablehlo_donors(stablehlo)
    aliased = set(hlo_alias_map(hlo))
    if not donors and not aliased:
        return  # program doesn't donate (dry-run cells): nothing to audit
    arg_types, result_types = stablehlo_main_types(stablehlo)

    dropped = sorted(donors - aliased)
    if dropped:
        shapes = [arg_types[i] if i < len(arg_types) else "?"
                  for i in dropped[:6]]
        report.add("program-donation", SEV_WARNING,
                   f"{len(dropped)} donated input(s) were not aliased to "
                   f"any output (args {dropped[:6]}: {shapes}) — the "
                   f"donation is silently dropped and the buffer is "
                   f"copied; an output dtype/shape drifted from its "
                   f"input, or the input is still live at the end of the "
                   f"step", where=f"args {dropped[:6]}")

    # state-shaped inputs that were never donated: an input whose exact
    # tensor type matches an un-aliased output is round-tripped state
    # paying a full copy per step (the train step's error-feedback
    # buffers were exactly this). Scalars and tiny tensors are ignored.
    aliased_out: Set[int] = set(hlo_alias_map(hlo).values())
    free_out_types = [t for i, t in enumerate(result_types)
                      if i not in aliased_out]
    suspects: List[int] = []
    for i, t in enumerate(arg_types):
        if i in donors or _elems(t) < 1024:
            continue
        if t in free_out_types:
            free_out_types.remove(t)  # one output matches one input
            suspects.append(i)
    if suspects:
        shapes = [arg_types[i] for i in suspects[:6]]
        report.add("program-donation", SEV_WARNING,
                   f"{len(suspects)} non-donated input(s) have the exact "
                   f"type of an un-aliased output (args {suspects[:6]}: "
                   f"{shapes}) — state round-tripped through the step "
                   f"without donation pays a device copy per call; add "
                   f"the argument to donate_argnums",
                   where=f"args {suspects[:6]}")


@register_pass("program-blocking-collective", kind="program",
               needs=("hlo",),
               doc="blocking ppermute/all-gather under a latency-hiding "
                   "schedule (gpu/tpu only)")
def _blocking_collective(ctx, report: LintReport) -> None:
    hlo = getattr(ctx, "hlo", None)
    if not hlo:
        return
    if getattr(ctx, "platform", "cpu") not in ("gpu", "tpu", "cuda",
                                               "rocm"):
        return  # CPU HLO has no async pairs; nothing to hide anyway
    if not getattr(ctx, "latency_hiding", False):
        return
    hits: List[Tuple[str, int]] = []
    for op in _BLOCKING_COLLECTIVES:
        # " op(" matches the synchronous form only: the async pair lowers
        # to "op-start(" / "op-done("
        blocking = len(re.findall(rf"(?<![\w-]){op}\(", hlo))
        if blocking:
            hits.append((op, blocking))
    for op, n in hits:
        report.add("program-blocking-collective", SEV_WARNING,
                   f"{n} blocking {op} op(s) in the compiled program "
                   f"while the latency-hiding scheduler is enabled — the "
                   f"collective serializes against compute instead of "
                   f"overlapping", where=op)
