"""Compile a cell's serving programs at its sizes (the configuration's, with
the traffic mix's) for a described TPU v5e and print what each needs
(``memory_analysis()``).

    JAX_PLATFORMS=cpu python bench/rehearse_memory.py cmdr-32b.doc_sessions

Nothing runs: the TPU compiler, installed beside the CPU backend, compiles
for a chip that is not attached. Each program's need is its arguments plus
outputs plus temporaries, less what is aliased. The weights, the KV pool
(twice: no program donates it, so each step holds the old pool and the new
one), the ranker and the largest program's temporaries must fit the chip;
the pool in the configuration file is sized from these numbers by hand.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
GB = 1e9


def main(names) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import arch, weights
    from bench.harness import load_cell
    from repro.serving.engine import RealBackend

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    shard = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=shard)

    for name in names:
        c = load_cell(name).config
        model = arch.load(c)
        sv, cfg, shapes = c["serving"], model.program_config(c), model.shapes(c)
        params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                              jax.eval_shape(lambda: weights.make(c, 0)))
        be = RealBackend(cfg, None, max_batch=sv["max_batch"],
                         cache_len=sv["cache_len"], prompt_len=sv["prompt_len"],
                         paged=True)
        bs, w = sv["block_size"], sv["cache_len"]
        be._paged_programs(bs)
        L, per_token = shapes.kv_row[0], shapes.kv_row[1:]
        pool = s((sv["kv_blocks"] + 1, L, bs) + per_token, c["dtype"])
        i32, key = jnp.int32, s((2,), jnp.uint32)
        tok = s((sv["max_batch"], 1), i32)
        chunk = sv["prefill_chunk_tokens"]
        # the widest dispatch of each kind: a step's chunk budget filled by
        # the smallest bucket, one chunk of a long prompt, the budget filled
        # by one-block remainders of prefix hits, decode at max_batch
        grid = {"prefill": {64: [min(chunk // 64, sv["max_batch"])],
                            chunk: [1]},
                "extend": {chunk: [1], bs: [min(chunk // bs, sv["max_batch"])]},
                "decode": [sv["max_batch"]]}
        cases = []
        for ln, bsz in grid["prefill"].items():
            b = max(bsz)
            cases.append((f"prefill_bucket L={ln} B={b}", be._prefill_bucket,
                          (params, s((b, ln), i32), s((b,), i32), key)))
            lane = s((L, b, w) + per_token, c["dtype"])
            cases.append((f"place_paged L={ln} B={b}", be._place_paged,
                          (pool, pool, lane, lane, tok, s((b,), i32),
                           s((b, -(-ln // bs)), i32), s((b,), i32))))
        for ln, bsz in grid["extend"].items():
            b = max(bsz)
            cases.append((f"extend_chunk_paged L={ln} B={b}",
                          be._extend_chunk_paged,
                          (params, pool, pool, tok, s((b, ln), i32),
                           s((b,), i32), s((b, w // bs), i32), s((b,), i32),
                           s((b,), jnp.bool_), key)))
        b = max(grid["decode"])
        cases.append((f"decode_paged B={b}", be._decode_paged,
                      (params, pool, pool, tok, s((b,), i32),
                       s((b, w // bs), i32), s((b,), i32), key)))
        wb = shapes.weight_bytes()
        pool_b = (sv["kv_blocks"] + 1) * bs * shapes.kv_bytes_per_token
        print(f"{name}: weights {wb['layers'] / GB:.3f} GB layers + "
              f"{wb['embedding_and_head'] / GB:.3f} GB embedding/head = "
              f"{wb['total'] / GB:.3f} GB; KV {shapes.kv_bytes_per_token} B/token;"
              f" pool K+V {pool_b / GB:.3f} GB for {sv['kv_blocks']} blocks",
              flush=True)
        for label, fn, args in cases:
            m = fn.lower(*args).compile().memory_analysis()
            need = (m.argument_size_in_bytes + m.output_size_in_bytes
                    + m.temp_size_in_bytes - m.alias_size_in_bytes)
            print(f"  {label}: args {m.argument_size_in_bytes / GB:.3f} out "
                  f"{m.output_size_in_bytes / GB:.3f} temp "
                  f"{m.temp_size_in_bytes / GB:.3f} alias "
                  f"{m.alias_size_in_bytes / GB:.3f} -> {need / GB:.3f} GB",
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]])
