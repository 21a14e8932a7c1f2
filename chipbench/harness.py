"""One run of one cell: set-up, followed rounds, timed window, check.

Everything a cell needs is found by name.  ``BENCHMARK.json`` names the
cell's configuration and traffic mix; the configuration is
``configs/<config>.json`` with the plain reference it names under
``references/``; the mix is ``traffic/<traffic>.json``; the limits of the
correctness check are ``limits/<cell>.json``; each per-layer metric is read
by ``metrics/<metric>.py``.

The system under test is ``repro.core.rounds.RoundDriver.run_round``, built
from the configuration and the mix and fed the benchmark's own batches,
fleet and initial weights, all drawn from ``--seed``.  Host spans
(``jax.profiler.TraceAnnotation``) are wrapped from here around the
driver's calls into its layers: the batch feed, the planner, the engine
step, the aggregation policy and the broadcast.

A run:

1. set-up: weights on the device in one jitted call from the seed, the
   driver built, and the first ``followed_rounds`` rounds run through the
   same ``run_round`` and feed as the window, which warms every program the
   window uses.  The per-step losses, the client replicas after the first
   local step and the global model after the last followed round are read
   for the check (that reading is not counted as set-up);
2. the window: closed-loop rounds for ``--seconds`` (with ``--trace 1``, the
   mix's ``trace_seconds`` under the profiler), each ending in
   ``jax.block_until_ready`` on the client parameters;
3. the device's peak memory is read, the program's state freed, and the
   plain reference replays the followed rounds; the compared numbers and
   their limits decide ``correct``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
sys.path.insert(0, str(ROOT / "src"))

from chipbench import traffic  # noqa: E402

# configuration-file keys -> the program's ArchConfig fields
ARCH_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "layer_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias", "use_qkv_bias": "qkv_bias",
    "compute_dtype": "dtype", "param_dtype": "param_dtype",
}
# a slice whose reference update is this far under the median slice's is
# moved by round-off alone and is left out of the norm comparisons
ROUNDOFF_SHARE = 1e-3


class NoChipError(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(name: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=cfg,
                mix=traffic.load_mix(w["traffic"]), limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def load_module(kind: str, name: str):
    """``references/<name>.py`` or ``metrics/<name>.py`` as a module."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the chip and JAX's set-up
# ---------------------------------------------------------------------------

def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChipError(f"no TPU: JAX sees platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChipError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devs)}")
    return devs[:chips]


def configure_jax() -> None:
    """The persistent compilation cache at ``<checkout>/.jax_cache`` for
    every program, so that only a checkout's first run compiles."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Backend compiles (persistent-cache reads included), timestamped."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events: List[tuple] = []          # (end time, seconds)

    def install(self) -> "CompileLog":
        import jax

        def on_duration(event, duration_secs, **_):
            if event == self.EVENT:
                self.events.append((time.perf_counter(), duration_secs))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self

    def between(self, t0: float, t1: float) -> List[float]:
        return [d for t, d in self.events if t0 <= t <= t1]


# ---------------------------------------------------------------------------
# the system under test, built from the cell
# ---------------------------------------------------------------------------

def program_config(cfg_file: Dict, **overrides):
    from repro.configs import get_config
    fields = {ARCH_FIELDS[k]: v for k, v in cfg_file.items()
              if k in ARCH_FIELDS}
    fields.update(overrides)
    return get_config(cfg_file["program_arch"]).with_overrides(**fields)


def make_weights(arch, seed: int) -> Dict:
    """The initial global model, in one jitted call on the device, as
    read-only host arrays.  The tree's layout is the program's; the values
    are the benchmark's: truncated normal / sqrt(fan_in) for projections,
    normal(0, 0.02) for embeddings and biases, ones for norms."""
    import jax
    import jax.numpy as jnp
    from repro.models import registry
    shapes = jax.eval_shape(lambda: registry.init_params(
        arch, jax.random.key(0)))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def draw(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if name.startswith("ln"):
                v = jnp.ones(s.shape, jnp.float32)
            elif name in ("embed", "bq", "bk", "bv"):
                v = 0.02 * jax.random.normal(k, s.shape, jnp.float32)
            else:
                v = (jax.random.truncated_normal(k, -3.0, 3.0, s.shape,
                                                 jnp.float32)
                     / math.sqrt(s.shape[-2]))
            out.append(v.astype(s.dtype))
        return out

    host = jax.device_get(draw(jax.random.key(seed)))
    for a in host:
        a.flags.writeable = False
    return jax.tree_util.tree_unflatten(treedef, host)


def build_driver(arch, mix: Dict, seeds: Dict, init: Dict, feed: Callable,
                 loss_fn: Optional[Callable] = None):
    import jax
    from repro.core import latency, rounds
    w = latency.workload_from_arch(arch, seq_len=mix["seq"],
                                   batch_size=mix["batch"],
                                   batches_per_epoch=mix["local_steps"],
                                   local_epochs=1)
    rc = rounds.RoundConfig(
        algorithm="fedpairing", engine=mix["engine"], rounds=1,
        batches_per_round=mix["local_steps"],
        participation=mix["participation"], drift_sigma_m=mix["drift_m"],
        split_policy=mix["split_policy"], lr=mix["lr"],
        seed=seeds["driver"])
    fl = traffic.make_fleet(mix["clients"], seeds["fleet"])
    fleet = latency.ClientFleet(positions=fl.positions, cpu_hz=fl.cpu_hz,
                                data_sizes=fl.data_sizes)
    driver = rounds.RoundDriver(
        arch, rc, fleet, chan=latency.ChannelModel(), workload=w,
        batch_fn=feed, init_fn=lambda key: init, loss_fn=loss_fn)
    return driver, fl


# ---------------------------------------------------------------------------
# spans around the driver's calls into its layers
# ---------------------------------------------------------------------------

class Spans:
    """Host spans, kept in memory; with ``tracing`` also written into the
    profiler's trace as ``chipbench.<name>``."""

    def __init__(self):
        self.tracing = False
        self.log: Dict[str, List[tuple]] = {}

    def wrap(self, name: str, fn: Callable) -> Callable:
        import jax
        log = self.log.setdefault(name, [])

        def spanned(*args, **kw):
            t0 = time.perf_counter()
            if self.tracing:
                with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
                    out = fn(*args, **kw)
            else:
                out = fn(*args, **kw)
            log.append((t0, time.perf_counter()))
            return out

        return spanned

    def within(self, name: str, t0: float, t1: float) -> List[tuple]:
        return [s for s in self.log.get(name, ()) if t0 <= s[0] <= t1]


class Recorder:
    """What the followed rounds hand to the check: host batches, per-step
    losses, and a hook on the first local step's replicas."""

    def __init__(self):
        self.on = True
        self.batches: List[Dict] = []
        self.losses: List[np.ndarray] = []
        self.first_step: Optional[Callable] = None
        self.check_s = 0.0          # time spent reading state for the check


def instrument(driver, spans: Spans, rec: Recorder) -> Callable[[], None]:
    """Wrap the driver's layer calls; returns the undo."""
    from repro.core import aggregation
    eng = driver._engine
    step = eng.step

    def recorded_step(params, batch, plan, agg_w):
        new, loss = step(params, batch, plan, agg_w)
        if rec.on:
            t0 = time.perf_counter()
            rec.losses.append(np.asarray(loss, np.float64))
            if rec.first_step is not None:
                rec.first_step(new)
                rec.first_step = None
            rec.check_s += time.perf_counter() - t0
        return new, loss

    eng.step = spans.wrap("step", recorded_step)
    driver.batch_fn = spans.wrap("batch_fn", driver.batch_fn)
    driver._adaptive_plan = spans.wrap("plan", driver._adaptive_plan)
    driver.agg_policy.apply = spans.wrap("aggregate", driver.agg_policy.apply)
    broadcast = aggregation.broadcast
    aggregation.broadcast = spans.wrap("broadcast", broadcast)

    def undo():
        aggregation.broadcast = broadcast

    return undo


def make_feed(stream: traffic.TokenStream, rec: Recorder,
              fault: Optional[Callable] = None) -> Callable[[], Dict]:
    import jax.numpy as jnp

    def feed():
        b = stream.next()
        if rec.on:
            rec.batches.append(b)
        if fault is not None:
            b = fault(b)
        return {k: jnp.asarray(v) for k, v in b.items()}

    return feed


# ---------------------------------------------------------------------------
# norms of parameter changes, by slice (one layer of a stacked leaf)
# ---------------------------------------------------------------------------

def change_norms(tree: Dict, init: Dict, clients: bool) -> List[np.ndarray]:
    """Per leaf, the norms of the change from the host tree ``init`` in
    each layer of a stacked leaf, or of the whole leaf otherwise: (N, W) /
    (N,) where ``tree`` is stacked over clients, (W,) / () where it is one
    model.  Computed on the device, one leaf of ``init`` there at a time."""
    import jax
    import jax.numpy as jnp

    def norms(a, b, keep):
        d = a.astype(jnp.float32) - b.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(d * d, axis=tuple(range(keep, d.ndim))))

    norms = jax.jit(norms, static_argnums=2)
    lead = 1 if clients else 0
    stacked = [str(getattr(path[0], "key", path[0])) == "blocks"
               for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return [np.asarray(norms(a, jnp.asarray(b), lead + st), np.float64)
            for st, a, b in zip(stacked, jax.tree_util.tree_leaves(tree),
                                jax.tree_util.tree_leaves(init))]


def norm_gap(program: List[np.ndarray], reference: List[np.ndarray]
             ) -> float:
    """Worst slice: |program norm - reference norm| over the larger of the
    reference slice's norm and the median moving slice's (layers a client
    neither ran nor lent out do not move, and count in the numerator only).
    Slices whose reference change is round-off, under ROUNDOFF_SHARE of
    that median but not 0, are left out."""
    p = np.concatenate([np.ravel(x) for x in program])
    r = np.concatenate([np.ravel(x) for x in reference])
    med = float(np.median(r[r > 0]))
    keep = ~((r > 0) & (r < ROUNDOFF_SHARE * med))
    gap = np.abs(p - r) / np.maximum(r, med)
    return float(np.max(np.where(keep, gap, 0.0)))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers read."""
    cell: Cell
    chips: int
    device_kind: str
    setup_end: float
    window: tuple                  # (t0, t1) of the measured window
    walls: List[float]
    step_calls: int
    tokens_per_round: int
    compile_log: CompileLog
    spans: Spans
    trace: Optional[Dict] = None   # tracereduce.reduce output


def paper_lengths(cpu_hz: np.ndarray, partner: np.ndarray, w: int
                  ) -> np.ndarray:
    """Eq. (6): L_i = floor(f_i / (f_i + f_j) W) for the lower-indexed
    member of a pair, clamped to [1, W-1]; the partner takes the rest."""
    out = np.full(len(partner), w, np.int64)
    for i, j in enumerate(partner):
        if i < j:
            li = int(np.floor(cpu_hz[i] / (cpu_hz[i] + cpu_hz[j]) * w))
            li = min(max(li, 1), w - 1)
            out[i], out[j] = li, w - li
    return out


def partner_of(pairs, n: int) -> np.ndarray:
    p = np.arange(n)
    for a, b in pairs:
        p[a], p[b] = b, a
    return p


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             arch_overrides: Optional[Dict] = None,
             plant: Optional[Dict] = None) -> Dict:
    """One run; returns the result line as a dict.  ``arch_overrides`` and
    ``plant`` serve the control and the planted-fault tests only."""
    import jax
    from repro.core.rounds import NonFiniteLossError
    plant = plant or {}
    if require_tpu:
        devices = require_chips(cell.chips)
        configure_jax()
    else:
        devices = jax.devices()[:cell.chips]
    compiles = CompileLog().install()
    mix, cfg_file = cell.mix, cell.config
    seeds = traffic.sub_seeds(seed)
    arch = program_config(cfg_file, **(arch_overrides or {}))
    init = make_weights(program_config(cfg_file), seeds["weights"])
    program_init = init
    if arch.param_dtype != cfg_file["param_dtype"]:
        program_init = jax.tree_util.tree_map(
            lambda a: a.astype(arch.param_dtype), init)
    rec, spans = Recorder(), Spans()
    stream = traffic.TokenStream(mix, cfg_file["vocab_size"], seeds["data"])
    driver, fleet = build_driver(arch, mix, seeds, program_init,
                                 make_feed(stream, rec, plant.get("batch")),
                                 plant.get("loss_fn"))
    undo = instrument(driver, spans, rec)
    if "step" in plant:
        driver._engine.step = plant["step"](driver._engine.step)
    n, k = mix["clients"], mix["local_steps"]
    try:
        # -- set-up: the followed rounds warm every program of the window
        state = driver.init_state()
        program_first = {}

        def first_step(new):
            program_first["norms"] = change_norms(new, init, clients=True)

        rec.first_step = first_step
        for _ in range(mix["followed_rounds"]):
            state = driver.run_round(state)
            jax.block_until_ready(state.client_params)
        t0 = time.perf_counter()
        program_change = change_norms(driver.global_params(state), init,
                                      clients=False)
        followed = [{"partner": partner_of(r.pairs, n),
                     "lengths": np.asarray(r.lengths, np.int64),
                     "batches": rec.batches[i * k:(i + 1) * k]}
                    for i, r in enumerate(state.history)]
        program_losses = np.stack(rec.losses).reshape(
            mix["followed_rounds"], k, n)
        rec.on = False
        rec.check_s += time.perf_counter() - t0

        # -- the window
        setup_end = time.perf_counter()
        window = min(seconds, mix["trace_seconds"]) if trace else seconds
        tracer = None
        if trace:
            from chipbench import tracereduce
            tracer = tracereduce.Tracer().start()
            spans.tracing = True
        walls, failed = [], 0
        run_round = spans.wrap("round", driver.run_round)
        t_w0 = time.perf_counter()
        while True:
            r0 = time.perf_counter()
            try:
                state = run_round(state)
                jax.block_until_ready(state.client_params)
            except NonFiniteLossError:
                failed += 1
                break
            r1 = time.perf_counter()
            walls.append(r1 - r0)
            if r1 - t_w0 >= window:
                break
        t_w1 = time.perf_counter()
        trace_data = None
        if tracer is not None:
            spans.tracing = False
            trace_data = tracer.stop_and_reduce()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        losses_ok = all(math.isfinite(h.mean_loss) for h in state.history)
        step_calls = len(spans.within("step", t_w0, t_w1))
        run = RunRecord(cell=cell, chips=len(devices),
                        device_kind=devices[0].device_kind,
                        setup_end=setup_end, window=(t_w0, t_w1),
                        walls=walls, step_calls=step_calls,
                        tokens_per_round=n * k * mix["batch"] * mix["seq"],
                        compile_log=compiles, spans=spans, trace=trace_data)
        setup_s = setup_end - t_start - rec.check_s
    finally:
        undo()
    del state, driver
    gc.collect()

    # -- the reference replays the followed rounds
    checks = compare(cell, init, followed, fleet, program_losses,
                     program_first["norms"], program_change)
    correct = (losses_ok and failed == 0 and len(walls) > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    metrics = (per_layer_metrics(run) if trace
               else end_to_end_metrics(run, setup_s, peak))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace_data:
        device["busy_s"] = trace_data["busy_s"]
        device["window_s"] = trace_data["window_s"]
        breakdown = trace_data["breakdown"]
    return result_line(correct, len(walls) + failed, failed, metrics, device,
                       checks, breakdown)


def compare(cell: Cell, init: Dict, followed: List[Dict], fleet,
            program_losses: np.ndarray, program_first: List[np.ndarray],
            program_change: List[np.ndarray]) -> Dict:
    """The compared numbers, each with its limit."""
    from chipbench import fedref
    ref_mod = load_module("references", cell.config["reference"])
    ref_first: Dict = {}

    def on_first(reps):
        ref_first["norms"] = [change_norms(r, init, clients=False)
                              for r in reps]

    out = fedref.follow_rounds(init, followed, fleet.data_sizes,
                               cell.mix["lr"], ref_mod.flow_loss,
                               ref_mod.config_view(cell.config), on_first)
    ref_losses = out["losses"]
    loss_gap = float(np.max(np.abs(program_losses - ref_losses)
                            / np.abs(ref_losses)))
    n = len(fleet.data_sizes)
    first = max(norm_gap([x[i] for x in program_first],
                         ref_first["norms"][i]) for i in range(n))
    change = norm_gap(program_change,
                      change_norms(out["global"], init, clients=False))
    lim = cell.limits
    checks = {
        "loss": {"value": loss_gap, "limit": lim["loss"]},
        "update1": {"value": first, "limit": lim["update1"]},
        "change": {"value": change, "limit": lim["change"]},
    }
    if cell.mix["split_policy"] == "paper":
        w = cell.config["num_hidden_layers"]
        bad = sum(int(np.any(paper_lengths(fleet.cpu_hz, f["partner"], w)
                             != f["lengths"])) for f in followed)
        checks["plan"] = {"value": bad, "limit": 0}
    return checks


# ---------------------------------------------------------------------------
# metrics and the result line
# ---------------------------------------------------------------------------

def end_to_end_metrics(run: RunRecord, setup_s: float, peak: int) -> Dict:
    t0, t1 = run.window
    rounds = len(run.walls)
    values = {
        "tokens_per_s": (rounds * run.tokens_per_round / (t1 - t0)
                         if rounds else None),
        "round_p90_s": (statistics.quantiles(run.walls, n=10)[-1]
                        if rounds >= 2 else None),
        "peak_hbm_gb": peak / 1e9,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in run.cell.end_to_end
            if values.get(m["name"]) is not None}


def per_layer_metrics(run: RunRecord) -> Dict:
    out = {}
    for m in run.cell.per_layer:
        v = load_module("metrics", m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result_line(correct: bool, attempted: int, failed: int, metrics: Dict,
                device: Dict, checks: Dict, breakdown: Optional[Dict] = None
                ) -> Dict:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def check_lines(checks: Dict) -> List[str]:
    return [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
            for k, c in checks.items()]
