"""A traced run hands the readers the scope reduction, an untraced run
reads no compiled module's text, and every step of a window carries the
step's own counters. At the tiny cell's size on the CPU: the
CPU's profile holds the window's host spans but no TPU plane, so the
device operations of the traced window are planted into what
``scopes.load`` read, one under each of four scopes, named by the
instructions of the compiled step's own scope map."""

import json

import pytest

import devtrace
import harness
import peaks
import scopes
import tiny

LAYERS = {"ce_head": 3e6, "attention": 2e6, "mlp": 1e6, "optimizer": 4e6}


def test_traced_run_reads_the_scopes(monkeypatch):
    maps, chosen = {}, {}
    real_map, real_load = scopes.scope_map, scopes.load

    def scope_map(compiled):
        one = real_map(compiled)
        maps.update(one)
        return one

    def load(trace_dir):
        trace = real_load(trace_dir)
        (w0, _), = [(s, d) for n, s, d in trace["host"] if n == "window"]
        (ops,) = maps.values()
        first = {}
        for name, path in ops.items():
            if name.split(".")[0] not in devtrace.CONTAINERS:
                first.setdefault(scopes.layer_of(path), name)
        t, events = w0, []
        for layer, dur in LAYERS.items():
            events.append([first[layer], t, dur])
            chosen[layer] = ops[first[layer]]
            t += dur
        return dict(trace, devices={"TPU:0": events}, modules={})

    monkeypatch.setattr(scopes, "scope_map", scope_map)
    monkeypatch.setattr(scopes, "load", load)
    monkeypatch.setattr(peaks, "peak",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    cell = tiny.cell()
    cell.per_layer = json.loads(
        (harness.ROOT / "BENCHMARK.json").read_text())["per_layer"]
    out = harness.execute(cell, 7, 0.5, True, require_tpu=False,
                          log=lambda m: None)
    assert out["correct"], out["checks"]
    n = out["attempted"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for layer, dur in LAYERS.items():
        assert got[f"{layer}_ms"] == pytest.approx(dur * 1e-6 / n)
    remat = sum(dur for layer, dur in LAYERS.items()
                if scopes.REMAT in chosen[layer].split("/"))
    assert got["recompute_share"] == pytest.approx(
        100 * remat / sum(LAYERS.values()))
    assert 0 < got["ce_head_roofline"]
    assert out["device"]["busy_s"] == pytest.approx(sum(LAYERS.values())
                                                    * 1e-9)


def test_untraced_run_reads_no_module_text(monkeypatch):
    def refuse(compiled):
        raise AssertionError("as_text() in an untraced run")

    monkeypatch.setattr(scopes, "scope_map", refuse)
    out = tiny.run()
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


def test_window_steps_carry_the_step_counters():
    run = harness.Run(tiny.cell(), 7, log=lambda m: None)
    run.prepare(require_tpu=False)
    steps = run.window(0.5)["steps"]
    assert steps
    for s in steps:
        assert "loss" not in s["counters"]
        assert {"tokens", "grad_norm", "lr"} <= set(s["counters"])
        assert s["counters"]["tokens"] == s["real"]
