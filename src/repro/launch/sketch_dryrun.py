import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import glava as glava_cfg
from repro.core.distributed import distributed_edge_query, distributed_ingest
from repro.core.sketch import GLavaSketch
from repro.launch.mesh import make_production_mesh, dp_axes
from repro.roofline.analysis import parse_collectives, roofline_from_cost

"""Sketch-plane dry-run: the paper's OWN data structure lowered on the
production mesh — distributed ingest (stream over dp axes, rows over model,
psum merge) and batched edge queries, with roofline terms.  Complements the
40 arch cells with the paper-representative workload."""


def run(config_name: str, batch: int, multi_pod: bool, outdir: Path):
    cfg = getattr(glava_cfg, config_name.upper())
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = dp_axes(mesh)
    sketch = GLavaSketch.empty(cfg, jax.random.key(0))

    counters_sh = NamedSharding(mesh, P(None, "model", None))
    stream_sh = NamedSharding(mesh, P(dp))
    rep = NamedSharding(mesh, P())

    sk_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sketch
    )

    def ingest(counters, src, dst, w):
        import dataclasses

        sk = dataclasses.replace(sketch, counters=counters)
        out = distributed_ingest(mesh, sk, src, dst, w, stream_axes=dp, backend="onehot")
        return out.counters

    jf = jax.jit(
        ingest,
        in_shardings=(counters_sh, stream_sh, stream_sh, stream_sh),
        out_shardings=counters_sh,
        donate_argnums=(0,),
    )
    args = (
        jax.ShapeDtypeStruct(sketch.counters.shape, jnp.float32),
        jax.ShapeDtypeStruct((batch,), jnp.uint32),
        jax.ShapeDtypeStruct((batch,), jnp.uint32),
        jax.ShapeDtypeStruct((batch,), jnp.float32),
    )
    with mesh:
        compiled = jf.lower(*args).compile()
    print(compiled.memory_analysis())
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else dict(cost)
    colls = parse_collectives(compiled.as_text())
    # useful flops: one-hot matmul formulation = 2 * d * B * (wr + wc) per
    # chip-set; the paper-faithful scalar semantics is d*B adds — report the
    # MXU formulation as model flops (it IS the TPU algorithm).
    model_flops = 2.0 * cfg.depth * batch * (cfg.width_rows + cfg.width_cols)
    rf = roofline_from_cost(dict(cost), colls, mesh.size, model_flops)
    rec = {
        "cell": f"glava-{config_name}/ingest_{batch}",
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "sketch": dict(depth=cfg.depth, wr=cfg.width_rows, wc=cfg.width_cols),
        "roofline": rf.to_dict(),
        "collectives": colls,
    }

    # query path
    def query(counters, qs, qd):
        import dataclasses

        sk = dataclasses.replace(sketch, counters=counters)
        return distributed_edge_query(mesh, sk, qs, qd)

    jq = jax.jit(query, in_shardings=(counters_sh, rep, rep), out_shardings=rep)
    qargs = (
        args[0],
        jax.ShapeDtypeStruct((65536,), jnp.uint32),
        jax.ShapeDtypeStruct((65536,), jnp.uint32),
    )
    with mesh:
        cq = jq.lower(*qargs).compile()
    qcost = cq.cost_analysis()
    qcost = qcost[0] if isinstance(qcost, (list, tuple)) else dict(qcost)
    qcolls = parse_collectives(cq.as_text())
    qrf = roofline_from_cost(dict(qcost), qcolls, mesh.size, 2.0 * cfg.depth * 65536)
    rec["query_roofline"] = qrf.to_dict()

    outdir.mkdir(parents=True, exist_ok=True)
    out = outdir / f"glava__{config_name}__{rec['mesh']}.json"
    out.write_text(json.dumps(rec, indent=2))
    print(
        f"[sketch-dryrun] {rec['cell']} on {rec['mesh']}: ingest "
        f"compute={rf.compute_s*1e3:.2f}ms memory={rf.memory_s*1e3:.2f}ms "
        f"collective={rf.collective_s*1e3:.2f}ms dominant={rf.dominant}; "
        f"query dominant={qrf.dominant} ({qrf.step_time_lb*1e6:.0f}µs lb)"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="web", choices=["web", "base", "nonsquare"])
    ap.add_argument("--batch", type=int, default=1 << 20)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    for mp in (False, True):
        run(args.config, args.batch, mp, Path(args.out))


if __name__ == "__main__":
    main()
