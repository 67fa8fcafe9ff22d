"""The trace reduction: busy and idle time, collective time, top device
operations and idle gaps labelled by the host span that covers them."""

import gzip
import json
from pathlib import Path

import pytest

import devtrace
import scopes

DATA = Path(__file__).resolve().parent / "data"

# two devices, a 100 ns window from t=1000; ops overlap on device 0
HAND = {
    "host": [["window", 1000.0, 100.0], ["dispatch", 1000.0, 10.0],
             ["plan", 1010.0, 45.0], ["block", 1055.0, 45.0]],
    "devices": {
        "TPU:0": [["fusion.1", 990.0, 30.0], ["fusion.2", 1010.0, 20.0],
                  ["all-gather.3", 1060.0, 20.0]],
        "TPU:1": [["fusion.1", 1000.0, 40.0],
                  ["collective-permute-done.4", 1050.0, 10.0],
                  ["fusion.9", 1200.0, 50.0]],
    },
}


def test_hand_counted_trace():
    r = devtrace.reduce(HAND, n_steps=2)
    # device 0 busy [1000,1030) + [1060,1080) = 50; device 1: 40 + 10 = 50
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["idle_share"] == pytest.approx(0.5)
    # collectives: 20 on device 0, 10 on device 1 -> 15 a device, 2 steps
    assert r["collective_s_per_step"] == pytest.approx(7.5e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((20 + 40) / 2 * 1e-9)
    assert "fusion.9" not in ops          # outside the window
    gaps = r["idle_gaps"]
    # device 0: [1030,1060) mostly under plan, [1080,1100) under block;
    # device 1: [1040,1050) under plan, [1060,1100) under block
    assert [g[1] for g in gaps] == pytest.approx(
        [40e-9, 30e-9, 20e-9, 10e-9])
    assert [g[0] for g in gaps] == ["block", "plan", "block", "plan"]


def test_one_window_is_required():
    bad = dict(HAND, host=[h for h in HAND["host"] if h[0] != "window"])
    with pytest.raises(ValueError):
        devtrace.reduce(bad, 1)


def _sweep_busy(events, w0, w1):
    """Busy time by a coverage sweep over +1/-1 edges (a second method)."""
    edges = []
    for _, s, d in events:
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort(key=lambda x: (x[0], -x[1]))
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_v5e_trace():
    """5 ms of a traced window of qwen3-4b.github-12k on one TPU v5e
    (the benchmark's own spans; device ops from the profiler's ``XLA Ops``
    line, names cut to the HLO instruction)."""
    with gzip.open(DATA / "trace-v5e-1chip.json.gz", "rt") as f:
        trace = json.load(f)
    (w0, w1), = [(s, s + d) for n, s, d in trace["host"] if n == "window"]
    r = devtrace.reduce(trace, n_steps=1)
    busy = _sweep_busy(trace["devices"]["TPU:0"], w0, w1)
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert r["idle_share"] == pytest.approx(1 - busy / (w1 - w0))
    assert r["collective_s_per_step"] == 0.0
    idle = sum(g for _, g in r["idle_gaps"])
    assert idle <= (w1 - w0 - busy) / 1e9 + 1e-12
    assert all(label in devtrace.HOST_SPANS + ("none",)
               for label, _ in r["idle_gaps"])
    assert r["device_ops"] == sorted(r["device_ops"], key=lambda x: -x[1])


def _xspace(planes) -> str:
    """Text proto of an XSpace: ``{plane: {line: [[name, start_ns,
    dur_ns], ...]}}``, each event at ``offset_ps`` from a line at 0."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = {}
        body = []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = " ".join(
                f"events {{ metadata_id: "
                f"{names.setdefault(n, len(names) + 1)} "
                f"offset_ps: {int(s * 1000)} duration_ps: {int(d * 1000)} }}"
                for n, s, d in events)
            body.append(f'lines {{ id: {lid} name: "{line}" '
                        f'timestamp_ns: 0 {evs} }}')
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in names.items())
        out.append(f'planes {{ id: {pid} name: "{plane}" '
                   f'{" ".join(body)} {meta} }}')
    return "\n".join(out)


def test_loaders_agree_on_the_recorded_trace(tmp_path):
    """The recorded v5e trace written back as an ``.xplane.pb``, with an
    ``XLA Modules`` line and the program's ``repro.*`` spans added:
    ``scopes.load`` reads what ``devtrace.load`` reads and more, and
    ``devtrace.reduce`` gives the same numbers from either, and from the
    recorded trace itself."""
    from jax.profiler import ProfileData
    with gzip.open(DATA / "trace-v5e-1chip.json.gz", "rt") as f:
        trace = json.load(f)
    (w0, wd), = [(s, d) for n, s, d in trace["host"] if n == "window"]
    modules = [["jit_step(7)", w0, wd / 2], ["jit_step(7)", w0 + wd / 2,
                                             wd / 2]]
    spans = [["repro.plan_batch", w0 + 10.0, 100.0],
             ["repro.materialize", w0 + 200.0, 50.0]]
    text = _xspace({
        "/device:TPU:0": {devtrace.OPS_LINE: trace["devices"]["TPU:0"],
                          scopes.MODULES_LINE: modules},
        "/host:CPU": {"python": trace["host"] + spans
                      + [["unrelated", w0, 5.0]]}})
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    bare, full = devtrace.load(str(tmp_path)), scopes.load(str(tmp_path))
    assert bare["devices"] == trace["devices"]
    assert sorted(bare["host"]) == sorted(trace["host"])
    assert full["devices"] == bare["devices"] and full["host"] == bare["host"]
    assert full["modules"] == {"TPU:0": modules}
    assert sorted(full["spans"]) == sorted(spans)
    r = devtrace.reduce(full, n_steps=1)
    assert r == devtrace.reduce(bare, n_steps=1)
    assert r == pytest.approx(devtrace.reduce(trace, n_steps=1))
