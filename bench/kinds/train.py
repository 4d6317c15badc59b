"""Training cells: AMB epochs of the program through ``AMBSession.run``.

Set-up builds one session (weights from the seed, made on the device by
``bench.weights``), drives it through its first ``check_steps`` epochs by
the same ``run`` call and prefetched feed that the window uses, and reads
what the reference compares: each step's loss, the norm of every leaf of
the dual after step 1 (the first gradient as the optimizer got it), and
of the stored weights' change from w0 after the last set-up step (what
the next step runs on).  The
window then runs whole epochs until ``--seconds`` have passed.  Once it
has closed and the peak memory is read, the program is freed and the
float32 reference (``bench.reference``) repeats the set-up epochs from
the same seed, on the same token rows and b_i(t), both made by
``bench.traffic`` and not by the program.

Every epoch's b_i(t), set-up and window, has to be the one the straggler
model and the Lemma-6 budget give (``bench.traffic.batch_sizes``), and
the credited tokens are counted from that schedule.

Traffic keys (``bench/workloads/<cell>.json``): ``workers``, ``seq_len``,
``batch_per_worker``, ``tokens`` (the token law), ``clock`` (a
``ClockSpec`` of the program), ``beta`` (``k``, ``scale``; mu is the
global batch), ``schedule_seed`` (the straggler draws), ``prefetch``,
``check_steps`` and ``limits``.  Consensus is exact: the reference has no
other.
"""
from __future__ import annotations

import gc
import math
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, reference, traffic as traffic_mod, weights

SPAN_WINDOW = "bench.window"
SPAN_RUN = "AMBSession.run"
SPAN_READY = "bench.block_until_ready"
HOST_SPANS = (SPAN_WINDOW, SPAN_RUN, SPAN_READY)
MAX_EPOCHS = 1 << 30


class _WindowClosed(Exception):
    """Raised after the epoch that ends at or past ``--seconds``."""


def program_config(conf: dict):
    """The program's ``ArchConfig`` for the sizes of ``conf``: its dense
    decoder at the file's widths and depth; ``program_options`` holds
    any further ``ArchConfig`` field the file sets."""
    from repro.models.common import ArchConfig
    return ArchConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=conf["qkv_bias"], rope_theta=conf["rope_theta"],
        dtype=conf["torch_dtype"], **conf.get("program_options", {}))


@jax.jit
def _change_norms(params, w0):
    """(leaves,) norm of every stored leaf's change from w0."""
    return jnp.stack([jnp.linalg.norm((p.astype(jnp.float32) - w).reshape(-1))
                      for p, w in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(w0))])


def _session(conf, traffic, seed, devices):
    from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec
    from repro.dist import use_sharding
    from repro.dist.params import tree_shardings
    from repro.launch.mesh import make_mesh
    from repro.models import init_params

    n = traffic["workers"]
    cfg = program_config(conf)
    mesh = make_mesh((n, 1), ("data", "model"), devices=devices)
    with use_sharding(mesh):
        shape = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        ours = weights.abstract(conf)
        if (jax.tree.structure(shape) != jax.tree.structure(ours)
                or jax.tree.leaves(shape) != jax.tree.leaves(ours)):
            raise ValueError("the program's parameter tree differs from "
                             "bench/weights.py")
        params = weights.make(conf, seed, tree_shardings(shape, mesh))
    train = TrainSpec(arch=conf["program_arch"], data=n, model=1,
                      seq_len=traffic["seq_len"],
                      batch_per_worker=traffic["batch_per_worker"],
                      optimizer="dual_averaging",
                      seed=traffic["schedule_seed"])
    cons = ConsensusSpec(consensus="exact", beta_k=traffic["beta"]["k"],
                         beta_scale=traffic["beta"]["scale"])
    return AMBSession(train, ClockSpec(**traffic["clock"]), cons,
                      mesh=mesh, params=params, cfg=cfg)


def source(conf, traffic, seed) -> traffic_mod.TokenRows:
    return traffic_mod.TokenRows(traffic, conf["vocab_size"], seed)


def setup(conf, traffic, seed, devices):
    """Session, feed and the program's readings after the set-up epochs."""
    session = _session(conf, traffic, seed, devices)
    feed = source(conf, traffic, seed)
    steps = traffic["check_steps"]
    rec = {"loss": [], "b": []}

    def on_step(epoch, m):
        rec["loss"].append(m["loss"])
        rec["b"].append(np.asarray(m["b"]))

    kw = dict(prefetch=traffic["prefetch"], on_step=on_step)
    session.run(1, feed, **kw)
    grad1 = np.asarray(reference.leaf_norms(session.state["opt"]["z"]))
    session.run(steps - 1, feed, **kw)
    state = session.state
    param = np.asarray(_change_norms(state["params"], state["opt"]["w0"]))
    return session, feed, {"loss": rec["loss"], "b": rec["b"],
                           "grad1": grad1, "param": param}


def reference_readings(conf, traffic, seed, devices, quant=None):
    """The reference's readings of the set-up epochs, on the rows and
    b_i(t) of ``bench.traffic`` (``quant`` picks the lower-precision
    control)."""
    steps = traffic["check_steps"]
    feed = source(conf, traffic, seed)
    batches = [feed.rows(t) for t in range(steps)]
    bs = traffic_mod.batch_sizes(traffic, range(steps))
    beta_kw = {"k": traffic["beta"]["k"], "scale": traffic["beta"]["scale"],
               "mu": float(feed.global_batch)}
    w0 = weights.make(conf, seed, jax.sharding.SingleDeviceSharding(
        devices[0]))
    with jax.default_matmul_precision("highest"):
        return reference.run_exact(conf, w0, batches, bs, steps, beta_kw,
                                   quant=quant)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared: the loss, first gradient and stored weights'
    change gaps."""
    keep = reference.moving_leaves(ref["grad1"])
    return {"loss_gap": reference.loss_gap(prog["loss"], ref["loss"]),
            "grad_gap": reference.worst_gap(prog["grad1"], ref["grad1"]),
            "param_gap": reference.worst_gap(
                prog["param"], ref["param"],
                keep & reference.large_changes(ref["param"]))}


def schedule_misses(traffic, epochs, bs) -> int:
    """Epochs whose b_i(t) differ from the straggler schedule's."""
    want = traffic_mod.batch_sizes(traffic, epochs)
    return int(sum(np.any(np.asarray(b) != w) for b, w in zip(bs, want)))


def judge(numbers: dict, limits: dict) -> tuple[list, bool]:
    """[[name, value, limit], ...] and whether every value is in bounds."""
    checks = [[k, numbers[k], limits[k]] for k in limits]
    return checks, all(math.isfinite(v) and v <= lim for _, v, lim in checks)


def run(ctx) -> dict:
    """One run of a training cell; see the module docstring."""
    conf, traffic = ctx.config, ctx.traffic
    n = traffic["workers"]
    devices = jax.devices()[:n]
    session, feed, prog = setup(conf, traffic, ctx.seed, devices)
    win, epochs = [], []

    def on_window_step(epoch, m):
        win.append(m)
        epochs.append(epoch)
        if time.perf_counter() - t0 >= ctx.seconds:
            raise _WindowClosed
    compiles0 = ctx.timer.compiles
    if ctx.trace:
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(harness.TRACE_DIR))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(SPAN_WINDOW):
        with jax.profiler.TraceAnnotation(SPAN_RUN):
            try:
                session.run(MAX_EPOCHS, feed, prefetch=traffic["prefetch"],
                            on_step=on_window_step)
            except _WindowClosed:
                pass
        with jax.profiler.TraceAnnotation(SPAN_READY):
            jax.block_until_ready(session.state)
    t1 = time.perf_counter()
    if ctx.trace:
        jax.profiler.stop_trace()
    compiles = ctx.timer.compiles - compiles0
    peak = harness.memory_peak_bytes(devices)
    session.close()
    del session, feed
    gc.collect()

    ref = reference_readings(conf, traffic, ctx.seed, devices)
    numbers = compare(prog, ref)
    steps = traffic["check_steps"]
    numbers["schedule_misses"] = schedule_misses(
        traffic, list(range(steps)) + epochs,
        prog["b"] + [m["b"] for m in win])
    checks, in_bounds = judge(numbers, traffic["limits"])
    failed = sum(1 for m in win if not math.isfinite(m["loss"]))
    correct = failed == 0 and in_bounds
    credited = (int(traffic_mod.batch_sizes(traffic, epochs).sum())
                * traffic["seq_len"]) if epochs else 0
    computed = len(win) * n * traffic["batch_per_worker"] * traffic["seq_len"]
    return {"correct": correct, "attempted": len(win), "failed": failed,
            "checks": checks,
            "end_to_end": {"train_tokens_per_s": credited / (t1 - t0)},
            "setup_s": t0 - ctx.t_start, "window_s": t1 - t0,
            "epochs": len(win), "credited_tokens": credited,
            "step_s": sum(m["step_s"] for m in win),
            "credited_share": credited / max(computed, 1),
            "compiles_in_window": compiles, "memory_peak_bytes": peak,
            "host_spans": HOST_SPANS, "window_span": SPAN_WINDOW}
