"""Serving-engine benchmarks on the paged, prefix-cached KV pool.

``serving_engine`` contrasts chunked prefill co-scheduled with decode vs
naive stop-the-world prefill on a skewed ("github" preset) request trace.
``paged_kv`` is the acceptance row for the paged pool itself: a shared
system-prompt trace must feed >= 40% fewer prefill tokens with the prefix
cache on than off while emitting bitwise-identical outputs, and a
mixed-length trace must admit strictly more concurrent requests than the
old slot pool could at equal device memory (a slot pool pins
``context_limit + max_new`` rows per admitted request; pages are charged
per token actually held).

Drives ``repro.launch.serve.serve()`` in this process on a 1x1 mesh (a
process that holds JAX cannot hand the device to a child) and reads the
stats it returns.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

__all__ = ["paged_kv", "serving_engine"]


def _run_serve(tag: str, extra: Sequence[str], *, n_req: int) -> Dict:
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import build_parser, serve
    args = build_parser().parse_args([
        "--arch", "gemma3-1b", "--reduced",
        "--trace", "github", "--requests", str(n_req),
        "--context-limit", "96", "--max-new", "8", *extra])
    rc, out = serve(args, make_mesh((1, 1), ("data", "model")))
    if rc != 0:
        raise RuntimeError(f"serve run failed ({tag}): rc={rc} "
                           f"{out and out['error']}")
    return out


def _run_mode(mode: str, *, quick: bool) -> Dict:
    n_req = 16 if quick else 32
    # --passes 2 and read the WARM pass: pass 0's TTFT/tokens-per-s are
    # dominated by the one-time XLA engine compile, which would drown the
    # scheduling signal this row exists to measure
    out = _run_serve(f"mode-{mode}", [
        "--arrival-rate", "3.0", "--k", "2",
        "--items", "4", "--cap-t", "32", "--page-sz", "16",
        "--prefill-mode", mode, "--passes", "2"], n_req=n_req)
    return out["passes"][1]


def serving_engine(quick: bool = True) -> List[Dict]:
    rows = []
    for mode in ("interleaved", "serial"):
        st = _run_mode(mode, quick=quick)
        rows.append({
            "prefill_mode": mode,
            "completed": st["completed"],
            "steps": st["steps"],
            "tokens_per_s": st["tokens_per_s"],
            "ttft_s_p50": st["ttft_s_p50"],
            "ttft_s_p95": st["ttft_s_p95"],
            "ttft_steps_p95": st["ttft_steps_p95"],
            "tpot_s_p50": st["tpot_s_p50"],
            "tpot_s_p95": st["tpot_s_p95"],
            "kv_occupancy": st["kv_pool"]["mean_occupancy"],
            "kv_peak_pages": st["kv_pool"]["peak_in_use"],
            "spec_acceptance": st["speculative"]["acceptance_rate"],
            "spec_tokens_per_tick": st["speculative"]["tokens_per_tick"],
            "fresh_compiles": st["fresh_compiles"],
        })
    return rows


def paged_kv(quick: bool = True) -> List[Dict]:
    rows: List[Dict] = []
    # --- prefix cache: shared system prompt, cache on vs off ------------
    n_req = 12 if quick else 24
    common = ["--system-prompt", "48", "--arrival-rate", "0.5",
              "--items", "4", "--cap-t", "32", "--page-sz", "16",
              "--seed", "1"]
    on = _run_serve("prefix-on", common, n_req=n_req)["passes"][0]
    off = _run_serve("prefix-off", common + ["--no-prefix-cache"],
                     n_req=n_req)["passes"][0]
    fed_on = on["prefill_tokens_fed"]
    fed_off = off["prefill_tokens_fed"]
    saving = (fed_off - fed_on) / max(fed_off, 1)
    outputs_equal = on["outputs"] == off["outputs"]
    row = {
        "row": "prefix_cache",
        "requests": n_req,
        "system_prompt_tokens": 48,
        "prefill_fed_cache_on": fed_on,
        "prefill_fed_cache_off": fed_off,
        "prefill_saving_frac": round(saving, 4),
        "prefix_hit_rows": on["kv_pool"]["prefix_hit_rows"],
        "prefix_hit_pages": on["kv_pool"]["prefix_hit_pages"],
        "cow_copies": on["kv_pool"]["cow_copies"],
        "outputs_bitwise_equal": outputs_equal,
    }
    assert outputs_equal, "prefix cache changed the emitted ids"
    assert on["kv_pool"]["prefix_hit_rows"] > 0, "no prefix hits"
    assert saving >= 0.40, f"prefill saving {saving:.2%} < 40%"
    rows.append(row)
    # --- concurrency at equal device memory -----------------------------
    # the old slot pool pinned (context_limit + max_new) = 104 rows per
    # admitted request; give the paged pool the memory of FOUR such slots
    # (416 rows = 26 pages of 16) and pile up a skewed trace — peak
    # concurrent page tables must beat the 4-request slot ceiling
    equiv_slots = 4
    st = _run_serve("concurrency", [
        "--arrival-rate", "8.0", "--pages", "26", "--page-sz", "16",
        "--items", "4", "--cap-t", "32", "--seed", "3"],
        n_req=16 if quick else 32)["passes"][0]
    peak = st["kv_pool"]["peak_seqs"]
    row = {
        "row": "concurrency",
        "pool_rows": 26 * 16,
        "equiv_slots": equiv_slots,
        "peak_concurrent_seqs": peak,
        "peak_pages": st["kv_pool"]["peak_in_use"],
        "mean_occupancy": st["kv_pool"]["mean_occupancy"],
        "preemptions": st["kv_pool"]["preemptions"],
        "completed": st["completed"],
    }
    assert peak > equiv_slots, (
        f"paged pool admitted {peak} concurrent <= slot-equivalent "
        f"{equiv_slots} at equal memory")
    rows.append(row)
    return rows
