#!/usr/bin/env python3
"""Does a configuration's serving set-up fit one chip?  Compile-only.

Compiles the engine's two window programs (the decode step at
``(slots, 1)`` over the per-layer decode view, the prefill chunk at
``(slots, chunk)`` over the stacked weights) for a described TPU v5e
that is not attached, and adds up what the engine keeps resident:

    stacked weights + decode-view copy + 3 state arenas (decode pool,
    prefill scratch, staging pool) + the larger program's temporaries

against the chip's HBM from ``peaks.json``.  Nothing runs; no number it
prints is a measurement.

    JAX_PLATFORMS=cpu python benchmarks/chip/fitcheck.py mamba2-2.7b [--slots N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import catalog  # noqa: E402
import counts  # noqa: E402
import program  # noqa: E402

KIND = "TPU v5 lite"


def _bytes(tree) -> int:
    import jax
    import numpy as np
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


def fit(cfg: dict, device, slots: int = None) -> dict:
    """Resident and per-program bytes of ``cfg``'s engine on ``device`` (a
    described or real device), compiled at the window's shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models import build_model

    slots = slots or cfg["serve"]["slots"]
    chunk = cfg["serve"]["chunk"]
    mcfg = program.model_config(cfg)
    model = build_model(mcfg)
    shard = SingleDeviceSharding(device)

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shard),
            tree)

    params = program.abstract_params(cfg)
    view = jax.eval_shape(model.decode_view, params)
    cache = jax.eval_shape(lambda: model.init_cache(slots, 0, mcfg.dtype))
    i32 = jnp.int32
    decode = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i),
                     donate_argnums=(2,))
    chunk_step = jax.jit(lambda p, t, c, i: model.prefill_chunk(p, t, c, i),
                         donate_argnums=(2,))
    progs = {
        "decode": decode.lower(
            place(view), place(jax.ShapeDtypeStruct((slots, 1), i32)),
            place(cache), place(jax.ShapeDtypeStruct((slots,), i32))),
        "prefill_chunk": chunk_step.lower(
            place(params), place(jax.ShapeDtypeStruct((slots, chunk), i32)),
            place(cache), place(jax.ShapeDtypeStruct((slots,), i32))),
    }
    out = {"slots": slots, "chunk": chunk,
           "weights_bytes": _bytes(params), "decode_view_bytes": _bytes(view),
           "arena_bytes": _bytes(cache), "programs": {}}
    for name, lowered in progs.items():
        m = lowered.compile().memory_analysis()
        out["programs"][name] = {
            "temp_bytes": int(m.temp_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes)}
    extra = max(p["temp_bytes"] + p["output_bytes"] - p["alias_bytes"]
                for p in out["programs"].values())
    out["resident_bytes"] = (out["weights_bytes"] + out["decode_view_bytes"] +
                             3 * out["arena_bytes"])
    out["peak_bytes"] = out["resident_bytes"] + extra
    out["hbm_bytes"] = int(counts.peak(KIND)["hbm_bytes"])
    out["fits"] = out["peak_bytes"] <= out["hbm_bytes"]
    return out


def described_device():
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return topo.devices[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--slots", type=int, default=None)
    args = ap.parse_args(argv)
    cfg = catalog.load_json(HERE / "configs" / f"{args.config}.json")
    res = fit(cfg, described_device(), args.slots)
    print(json.dumps(res, indent=1))
    return 0 if res["fits"] else 1


if __name__ == "__main__":
    sys.exit(main())
