#!/usr/bin/env python3
"""Compile one configuration's drain for a described TPU v5e, no chip needed.

    JAX_PLATFORMS=cpu python bench/compile_check.py tm-iris-paper-k4096

Prints ``memory_analysis`` of the packed ``_consume_many_replicated`` step
(the program ``TMService.tick`` runs) at the configuration's tenant count,
on one chip. Nothing runs: the numbers are the compiler's sizes, not
measurements.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import setup
    from repro.core import online as online_mod
    from repro.core.online import SessionState
    from repro.core.tm import TMState, init_runtime
    from repro.data import buffer as buf_mod
    from repro.kernels import dispatch

    jax.config.update("jax_enable_compilation_cache", False)
    conf = setup.load_config(args.config)
    cfg = setup.tm_config(conf)
    svc_c = conf["service"]
    K = conf["tenants"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"
    dispatch._CACHE.clear()
    assert not dispatch.resolve("auto").interpret
    shard = SingleDeviceSharding(topo.devices[0])
    buf = jax.eval_shape(lambda: buf_mod.make(
        svc_c["buffer_capacity"], cfg.n_features, packed=True))
    ss = SessionState(
        tm=TMState(ta_state=jax.ShapeDtypeStruct(
            (K, cfg.max_classes, cfg.max_clauses, cfg.n_literals),
            cfg.state_dtype)),
        buf=jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((K,) + a.shape, a.dtype), buf),
        step=jax.ShapeDtypeStruct((K,), jnp.int32),
    )
    rt = jax.eval_shape(lambda: init_runtime(cfg, s=conf["s_online"],
                                             T=conf["T"]))
    tree = (ss, rt, jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((K, 2), jnp.uint32))
    tree = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shard),
        tree)
    compiled = online_mod._consume_many_replicated.lower(
        cfg, svc_c["chunk"], *tree, monitor=False).compile()
    mem = compiled.memory_analysis()
    print(f"{args.config}: K={K} "
          f"argument_bytes={mem.argument_size_in_bytes} "
          f"output_bytes={mem.output_size_in_bytes} "
          f"temp_bytes={mem.temp_size_in_bytes} "
          f"alias_bytes={mem.alias_size_in_bytes} "
          f"kernel={'tpu_custom_call' in compiled.as_text()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
