#!/usr/bin/env python3
"""Compile one AMB train step for a described TPU v5e, with no chip attached.

Prints the compiler's memory plan (``memory_analysis()``) and the compile
seconds of one qwen2-1.5b train step at published widths, cut to
``--layers``, on a ``(workers, 1)`` (data, model) mesh over the devices of
a described ``v5e:2x2``.  What the TPU compiler refuses (a program over
the chip's HBM, an unaligned tile) it refuses here, before a chip is used.

  python scripts/rehearse_v5e.py --workers 1 --layers 12 --seq 256 --bpw 8
  python scripts/rehearse_v5e.py --workers 4 --layers 1 --seq 128 --bpw 2 \\
      --consensus gossip
  python scripts/rehearse_v5e.py --workers 1 --layers 12 --seq 1024 --bpw 3 \\
      --hlo step.hlo.txt

Runs on the CPU host (``JAX_PLATFORMS=cpu``); the compile seconds are host
timings of the TPU compiler, never a chip time.
"""
import argparse
import dataclasses
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
from jax.experimental import topologies                      # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.api.protocol import build_protocol                # noqa: E402
from repro.api.specs import ConsensusSpec                    # noqa: E402
from repro.configs import get_config                         # noqa: E402
from repro.dist import use_sharding                          # noqa: E402
from repro.dist.params import tree_shardings                 # noqa: E402
from repro.kernels import router                             # noqa: E402
from repro.launch.mesh import make_mesh                      # noqa: E402
from repro.models import init_params                         # noqa: E402
from repro.optim import make_optimizer                       # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workers", type=int, choices=(1, 4), default=1)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--bpw", type=int, default=8,
                    help="sequences per worker")
    ap.add_argument("--consensus", default="exact",
                    choices=("exact", "gossip"))
    ap.add_argument("--kernels", default="pallas", choices=("pallas", "ref"))
    ap.add_argument("--hlo", metavar="PATH",
                    help="write the compiled step's HLO text here: its op "
                         "names are the device ops of a profiler trace")
    args = ap.parse_args(argv)
    router.set_mode(args.kernels)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    n = args.workers
    mesh = make_mesh((n, 1), ("data", "model"), devices=topo.devices[:n])
    cfg = dataclasses.replace(get_config("qwen2-1.5b"),
                              num_layers=args.layers)
    gb = n * args.bpw
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data"))
    with use_sharding(mesh):
        p_abs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                   cfg))
        pshard = tree_shardings(p_abs, mesh)
        cons = ConsensusSpec(consensus=args.consensus)
        opt = make_optimizer("dual_averaging", beta=cons.beta(gb)) \
            if args.consensus == "exact" else None
        proto = build_protocol(cfg, mesh, cons.to_amb_config(gb, 0),
                               optimizer=opt)
        st_abs = jax.eval_shape(proto.init, p_abs)
        if args.consensus == "exact":
            shards = {"params": pshard, "t": rep,
                      "opt": {"z": pshard, "w0": pshard, "t": rep}}
        else:
            shards = {"z": jax.tree.map(lambda _: rows, p_abs),
                      "w0": pshard, "t": rep}
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            st_abs, shards)
        tok = jax.ShapeDtypeStruct((gb, args.seq), jnp.int32, sharding=rows)
        b = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep)
        t0 = time.perf_counter()
        compiled = jax.jit(proto.step, donate_argnums=0).lower(
            state, {"tokens": tok, "labels": tok}, b).compile()
        seconds = time.perf_counter() - t0
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(compiled.as_text())
    ma = compiled.memory_analysis()
    gib = 2.0 ** 30
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(f"params={cfg.param_count()} workers={n} layers={args.layers} "
          f"consensus={args.consensus} kernels={args.kernels} "
          f"host_compile_s={seconds:.1f} "
          f"argument_gib={ma.argument_size_in_bytes / gib:.3f} "
          f"temp_gib={ma.temp_size_in_bytes / gib:.3f} "
          f"peak_gib={peak / gib:.3f} "
          f"tpu_custom_calls={compiled.as_text().count('tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
