"""The builder's compile check: the cells' kernels at their real sizes,
compiled for a TPU v5e that is described and not attached.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_check.py

Costs no chip time and runs nothing: it says whether the chip's compiler
takes each program and how much device memory it plans for (what the
compiler refuses here it refuses on the chip): the chunked scan and the
fused ragged ingest step at ``bge-small-10m``'s slab. A compile that passes
is not a chip run and is never reported as one.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

DIM = 384


def _report(name: str, lowered) -> None:
    t0 = time.perf_counter()
    try:
        compiled = lowered.compile()
    except Exception as e:  # the finding is the compiler's message
        print(f"{name}: REFUSED: {str(e).splitlines()[0][:300]}", flush=True)
        return
    m = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f}s; arguments "
          f"{m.argument_size_in_bytes / gib:.2f} GiB, outputs "
          f"{m.output_size_in_bytes / gib:.2f} (aliased "
          f"{m.alias_size_in_bytes / gib:.2f}), temporaries "
          f"{m.temp_size_in_bytes / gib:.3f}", flush=True)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from pathway_tpu.ops.knn import (KnnMetric, _fused_step_fns,
                                     _shared_search_fn, planned_capacity)

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype, sharding=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "bge-small-10m.json")) as f:
        rows = planned_capacity(
            json.load(f)["index"]["reserved_rows"])
    search = _shared_search_fn(3, KnnMetric.COS)
    for batch in (1, 8):
        _report(f"scan of {rows} rows, {batch} queries", search.lower(
            sds((batch, DIM), jnp.float32), sds((rows, DIM), jnp.bfloat16),
            (), sds((rows,), jnp.bool_)))
    from pathway_tpu.models.encoder import EncoderConfig, init_params
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    cfg = EncoderConfig.bge_small()
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    emb = JaxEncoderEmbedder(config=cfg, params=params, max_len=128,
                             ragged=True)
    step = _fused_step_fns(emb.ragged_device_producer, "bfloat16")
    for seqs in emb.ragged_buckets():
        docs = seqs * (128 // 16)
        tokens = sds((seqs, 128), jnp.int32)
        _report(f"fused ragged ingest, {seqs} x 128 tokens", step.lower(
            sds((rows, DIM), jnp.bfloat16), sds((rows,), jnp.bool_),
            sds((docs,), jnp.int32), params, tokens, tokens, tokens,
            sds((docs,), jnp.int32), sds((docs,), jnp.int32)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
