"""One run of one benchmark cell: set-up, the measured window, the check.

Set-up makes the weights on the device from the seed, plans the cell's
replay set (``bench/traffic``), loads or compiles every bucket it lands in,
and trains the first three batches through the window's own call and feed:
those steps are the ones compared with the plain reference. Every bucket
that they did not run is run once more. The window then repeats
plan -> materialize -> step -> ``block_until_ready`` over the replay set,
solving the next plan while the device runs the step, until ``seconds``
have passed; nothing compiles in it. After the window the program's state
is freed and the reference trains the same three batches.
"""

from __future__ import annotations

import gc
import importlib
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import check  # noqa: E402
import configs  # noqa: E402
import devtrace  # noqa: E402
import flops  # noqa: E402
import peaks  # noqa: E402
import scopes  # noqa: E402
import steppath  # noqa: E402
from traffic import gen  # noqa: E402

CHECKED_STEPS = 3
JAX_CACHE = BENCH / ".cache" / "jax"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    chips: int
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def reference(self):
        return importlib.import_module(f"configs.{self.config['reference']}")

    @property
    def program(self):
        return configs.program(self.config)

    @property
    def mesh_shape(self):
        return tuple(self.config["mesh"][str(self.chips)])


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` under ``root`` with its
    configuration, traffic mix, limits and metrics; raises at once where
    the configuration's program adapter is missing or incomplete."""
    man = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    configs.program(cfg)

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, config=cfg,
                traffic=gen.load_mix(w["traffic"], root / "bench" / "traffic"),
                chips=int(w["chips"]),
                limits=check.load_limits(name, root / "bench" / "limits"),
                end_to_end=[m for m in man["end_to_end"] if applies(m)],
                per_layer=[m for m in man["per_layer"] if applies(m)])


def configure_jax() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however quickly it compiles (not on the CPU, whose
    programs compile in seconds and warn when reloaded)."""
    import jax
    if jax.default_backend() == "cpu":
        return
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_tpu: bool = True):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


# -- the program's state in the benchmark's weights --------------------------

def program_leaf_norms(tree: Dict, n_layers: int) -> Dict:
    """Leaf norms of a program-layout tree under the reference's names
    (stage-stacked layers [d_p, L_s, ...] -> ``layers.<i>.<name>``)."""
    import jax
    import jax.numpy as jnp
    out = {}
    for name in ("embed", "unembed", "final_norm"):
        if name in tree:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(
                tree[name].astype(jnp.float32))))
    for path, x in jax.tree_util.tree_flatten_with_path(tree["stages"])[0]:
        leaf = jax.tree_util.keystr(path).split("'")[-2]
        r = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                             axis=tuple(range(2, x.ndim))))
        r = r.reshape(-1)
        for i in range(n_layers):
            out[f"layers.{i}.{leaf}"] = r[i]
    return out


@dataclass
class Run:
    cell: Cell
    seed: int
    log: Callable = print
    t_start: float = field(default_factory=time.perf_counter)

    # -- set-up --------------------------------------------------------------
    def prepare(self, require_tpu: bool = True, path=None) -> None:
        """Set-up; ``path`` reuses another run's ``StepPath`` (its
        resident executables) on the same devices."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_mesh
        from repro.optim import init_opt_state
        from repro.runtime.train_step import prepare_params

        cell, cfg = self.cell, self.cell.config
        self.devices = devices_for(cell.chips, require_tpu)
        bad = steppath.adamw_matches(cfg["training"])
        if bad:
            raise ValueError(f"the program's AdamW is not the "
                             f"configuration's: {bad}")
        self.mesh = path.mesh if path is not None else make_mesh(
            cell.mesh_shape, ("data", "model"), devices=self.devices)
        program = cell.program
        self.arch = program.arch(cfg)
        self.order, self.batches = gen.replay_set(
            cell.traffic, self.seed, int(cfg["vocab_size"]))
        self.corpora = [dict(enumerate(b)) for b in self.batches]
        on_cpu = self.devices[0].platform == "cpu"
        self.path = path or steppath.StepPath(
            self.arch, self.mesh, None if on_cpu else steppath.store_dir(ROOT),
            log=self.log)
        # plans in the mix's own order: the first pins the schedule, as the
        # bootstrap plan does in train(), so every seed lands in the same
        # buckets
        self.plans = [self.path.solve([len(d) for d in b])
                      for b in self.batches]
        self.steps = {}
        for p in self.plans:
            self.steps[str(self.path.key(p))] = self.path.get_step(p)
        builder = next(iter(self.steps.values()))[0]
        if builder.geom.v_stages != 1:
            raise NotImplementedError("interleaved layer stacking")
        pspecs, ospecs, _ = builder.specs(builder.abstract_params())
        named = lambda t: jax.tree.map(  # noqa: E731
            lambda s: NamedSharding(self.mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        ref = cell.reference

        # the canonical weights are made and stored in bf16 first: made in
        # the same program as the f32 master copy, the compiler may keep
        # the master unrounded (excess precision), and the master would no
        # longer start from the weights the step computes with
        weights = jax.jit(lambda k: ref.make_weights(cfg, k))

        def params_of(w):
            return prepare_params(self.arch, program.to_program(w), self.mesh,
                                  self.path.dtype, v_stages=1)

        def init(w):
            params = params_of(w)
            return params, init_opt_state(params)

        key = ref.run_key(self.seed)
        self._params_of = lambda k: jax.jit(
            params_of, out_shardings=named(pspecs))(weights(k))
        self.params, self.opt = jax.jit(
            init, out_shardings=(named(pspecs), named(ospecs)))(weights(key))
        self.readings = {"losses": [], "tokens_gap": 0}
        self.j = 0
        ran = set()
        while self.j < CHECKED_STEPS or ran != set(self.steps):
            b = self.order[self.j % len(self.order)]
            metrics = self._step(b)
            self.readings["losses"].append(float(metrics["loss"]))
            ran.add(str(self.path.key(self.plans[b])))
            self.j += 1
            if self.j == 1:
                self._read_grad()
            if self.j == CHECKED_STEPS:
                self._read_delta(key)
        self.log(f"[setup] buckets {sorted(self.steps)}; checked losses "
                 f"{self.readings['losses'][:CHECKED_STEPS]}")

    def _feed(self, b: int):
        return self.path.materialize(self.plans[b], self.corpora[b])

    def _call(self, fn, batch):
        """The compiled step on the run's state: ``(params, opt, metrics)``."""
        params, opt, _, metrics = fn(self.params, self.opt, None, batch)
        return params, opt, metrics

    def _step(self, b: int):
        """One step on batch ``b`` through the window's own call and feed;
        returns its metrics after ``block_until_ready``."""
        import jax
        _, fn = self.steps[str(self.path.key(self.plans[b]))]
        self.params, self.opt, metrics = self._call(fn, self._feed(b))
        jax.block_until_ready((self.params, metrics["loss"]))
        real = gen.real_tokens(self.batches[b])
        self.readings["tokens_gap"] = max(
            self.readings["tokens_gap"], abs(float(metrics["tokens"]) - real))
        return metrics

    def _read_grad(self) -> None:
        """The first gradient as AdamW received it: m_1 / (1 - beta1)."""
        import jax
        b1 = float(self.cell.config["training"]["beta1"])
        n = int(self.cell.config["num_hidden_layers"])
        norms = jax.jit(lambda m: program_leaf_norms(m, n))(self.opt["m"])
        self.readings["grad"] = {k: float(v) / (1 - b1)
                                 for k, v in norms.items()}

    def _read_delta(self, key) -> None:
        """The change since the seed's weights of the master weights and
        of the parameters the step returned (its compute copies)."""
        import jax
        import jax.numpy as jnp
        n = int(self.cell.config["num_hidden_layers"])
        p0 = self._params_of(key)
        change = jax.jit(lambda a, b: program_leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b), n))
        for name, now in (("delta", self.opt["master"]),
                          ("copy_delta", self.params)):
            self.readings[name] = {k: float(v)
                                   for k, v in change(now, p0).items()}
        del p0

    # -- the window ----------------------------------------------------------
    def window(self, seconds: float, trace_dir: Optional[str] = None) -> Dict:
        """Train over the replay set for ``seconds`` (with ``trace_dir``:
        one pass over it, under the profiler)."""
        import jax
        from jax.profiler import TraceAnnotation
        n_batches = len(self.order)
        steps: List[Dict] = []
        failed = 0

        def plan_next(j):
            b = self.order[j % n_batches]
            lengths = [len(d) for d in self.batches[b]]
            with TraceAnnotation("plan"):
                t0 = time.perf_counter()
                plan = self.path.solve(lengths)
                solve_ms = 1e3 * (time.perf_counter() - t0)
            self.plans[b] = plan
            with TraceAnnotation("materialize"):
                batch = self._feed(b)
            return b, plan, batch, solve_ms

        nxt = plan_next(self.j)
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        win = TraceAnnotation("window")
        win.__enter__()
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        t_end = t0
        while True:
            b, plan, batch, solve_ms = nxt
            key = str(self.path.key(plan))
            m0 = self.path.misses()
            with TraceAnnotation("dispatch"):
                self.steps[key] = self.path.get_step(plan)
                if self.path.misses() > m0:
                    failed += 1
                    self.log(f"[window] compiled bucket {key} inside the "
                             f"window")
                _, fn = self.steps[key]
                self.params, self.opt, metrics = self._call(fn, batch)
            self.j += 1
            last = trace_dir and len(steps) + 1 >= n_batches
            if not last:
                nxt = plan_next(self.j)
            with TraceAnnotation("block"):
                jax.block_until_ready((self.params, metrics["loss"]))
            t_end = time.perf_counter()
            real = gen.real_tokens(self.batches[b])
            self.readings["tokens_gap"] = max(
                self.readings["tokens_gap"],
                abs(float(metrics["tokens"]) - real))
            k = self.path.key(plan)
            steps.append({"batch": b, "bucket": key, "real": real,
                          "slots": k.n_chunks * k.cap, "solve_ms": solve_ms,
                          "docs": [len(d) for d in self.batches[b]]})
            if last or (not trace_dir and t_end - t0 >= seconds):
                break
        win.__exit__(None, None, None)
        if trace_dir:
            jax.profiler.stop_trace()
        elapsed = t_end - t0
        self.log(f"[window] {len(steps)} steps in {elapsed:.3f} s, "
                 f"{sum(s['real'] for s in steps)} real tokens, "
                 f"{failed} compiled inside")
        return {"steps": steps, "elapsed": elapsed, "failed": failed}

    # -- after the window -----------------------------------------------------
    def memory_peak(self) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return int(max(s.get("peak_bytes_in_use", 0) for s in stats))

    def scope_maps(self) -> Dict[str, Dict[str, str]]:
        """``scopes.scope_map`` of every resident bucket's compiled step."""
        maps = {}
        for _, compiled in self.steps.values():
            maps.update(scopes.scope_map(compiled))
        return maps

    def free(self) -> None:
        self.params = self.opt = None
        self.steps = {}
        self.path = None
        gc.collect()

    def reference(self, precision: str = "f32",
                  variant: Optional[str] = None) -> Dict:
        ref = self.cell.reference
        drop = variant == "half"
        batches = [ref.pack(self.batches[b], drop_half=drop)
                   for b in self.order[:CHECKED_STEPS]]
        return ref.train(self.cell.config, self.cell.config["training"],
                         self.seed, batches, precision=precision,
                         variant=None if drop else variant,
                         device=self.devices[0])


def per_layer(cell: Cell, win: Dict, reduced: Optional[Dict],
              scoped: Optional[Dict], peaks_by_bucket: Dict,
              bytes_limit: int, kind: str) -> Dict:
    """The cell's per-layer metrics of a window (``bench/metrics``)."""
    from metrics import reader
    model_flops = sum(flops.step_flops(cell.config, s["docs"])
                      for s in win["steps"])
    peak = peaks.peak(kind)
    run = {"steps": win["steps"], "peaks": peaks_by_bucket,
           "bytes_limit": bytes_limit, "trace": reduced, "scopes": scoped,
           "config": cell.config, "flops": model_flops, "chips": cell.chips,
           "peak_flops": peak["bf16_flops"],
           "peak_bytes_per_s": peak["hbm_bytes_per_s"]}
    out = {}
    for m in cell.per_layer:
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def bytes_limit_of(devices) -> int:
    return int(max((d.memory_stats() or {}).get("bytes_limit", 0)
                   for d in devices))


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, log: Callable = print,
            t_start: Optional[float] = None) -> Dict:
    """One run; returns the result line's object."""
    import tempfile
    configure_jax()
    run = Run(cell, seed, log=log, t_start=t_start or time.perf_counter())
    run.prepare(require_tpu=require_tpu)
    kind = run.devices[0].device_kind
    with tempfile.TemporaryDirectory() as tdir:
        win = run.window(seconds, trace_dir=tdir if trace else None)
        reduced = scoped = None
        if trace:
            n = len(win["steps"])
            maps = run.scope_maps()
            recorded = scopes.load(tdir)
            reduced = devtrace.reduce(recorded, n)
            scoped = scopes.reduce(recorded, maps, n)
    mem = run.memory_peak()
    peaks_by_bucket = dict(run.path.peaks)
    limit = bytes_limit_of(run.devices)
    run.free()
    ref = run.reference()
    nums = check.numbers(run.readings, ref)
    checks = check.verdict(nums, cell.limits)
    correct = all(c["ok"] for c in checks.values())
    device = {"platform": run.devices[0].platform, "kind": kind,
              "count": len(run.devices), "memory_peak_bytes": mem}
    if trace:
        metrics = per_layer(cell, win, reduced, scoped, peaks_by_bucket,
                            limit, kind)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        real = sum(s["real"] for s in win["steps"])
        metrics = {"tokens_per_s": {"value": real / win["elapsed"],
                                    "unit": "tokens/s"},
                   "setup_s": {"value": run.setup_s, "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
    out = {"correct": correct, "attempted": len(win["steps"]),
           "failed": win["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out
