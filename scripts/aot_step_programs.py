"""AOT-compile a configuration's step programs for a described v5e.

No chip: ``jax.experimental.topologies`` describes a ``v5e:2x2`` slice
and the TPU compiler compiles ``mixed_step``, ``decode_window`` and
``prefill`` of a ``chipbench/configs/<name>`` at its served sizes
(``serve.json``'s flags), from shapes alone. Printed a program: the
arguments' and temporaries' bytes (``memory_analysis``), one layer's K
slab, and by opcode the instructions of the optimized HLO whose result
is as large as the pool or a slab (PERF.md section 6, PRs 29 and 41).
``--dump DIR`` also writes each program's optimized HLO (custom calls'
payloads cut) to ``DIR/<config>.<program>.txt``: two checkouts' dumps
``diff`` to show a program unchanged.

``--time-lower`` compiles nothing: it prints the seconds each program
takes to TRACE and LOWER (``.lower()``; a Pallas kernel's Mosaic
lowering is part of it). That is what a warm start pays a program: the
compile cache's key is the lowered module, so a server that compiles
nothing still traces and lowers every program it warms up (PERF.md
section 6, PR 45). A small prefill is lowered and thrown away first (a
process's first lowering carries two seconds of JAX's own start-up),
then each program once: a second ``.lower()`` of the same program is a
cache hit of a millisecond. ``--repeat N`` does that N times, with
``jax.clear_caches()`` between, and prints the least of each (the
sandbox's CPU is shared: single readings swing by a third).

    JAX_PLATFORMS=cpu python3 scripts/aot_step_programs.py \
        --config olmoe-1b-7b [--tokens 512] [--dump /root/scratch/hlo]
    JAX_PLATFORMS=cpu python3 scripts/aot_step_programs.py \
        --config olmoe-1b-7b --time-lower

A compile that passes is not a chip run; nothing here is a device time.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

GIB = 2.0**30


def _flag(flags: list, name: str, default=None):
    return flags[flags.index(name) + 1] if name in flags else default


def _pool_sized(text: str, *pools: tuple) -> dict[str, int]:
    """opcode -> how many instructions give a result with as many
    elements as one of ``pools`` (a latent cache's second pool, the
    rotary keys, is narrower than its first) or as one layer's slab of
    it, in whatever shape of rows as wide as the pool's (the compiler
    scatters into the pool as ``[L*Hkv*N*bs, D]``). Parameters, bitcasts and tuple plumbing make no
    array. What may stand here: the in-place writers (``scatter`` and the
    fusion around it, the append kernels' ``custom-call``). A ``copy``,
    ``copy-start`` (a staging into another memory space: its result is a
    tuple, whose first shape is counted), ``slice``, ``dynamic-slice``,
    ``dynamic-update-slice`` or ``transpose`` of that size is a copy of
    the pool or of a slab."""
    sizes = set()  # (elements, the row's width)
    for pool in pools:
        slab = 1
        for d in pool[1:]:
            slab *= d
        sizes |= {(slab, pool[-1]), (slab * pool[0], pool[-1])}
    found: dict[str, int] = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]+)\]", line)
        # the opcode stands behind the result's type: ``...} copy(`` or,
        # behind a tuple's, ``...) copy-start(``
        op = m and re.search(r"[\]})] ([a-z][\w-]*)\(", line[m.end(1):])
        if not op:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        elems = 1
        for d in dims:
            elems *= d
        if (elems, dims[-1]) in sizes and op.group(1) not in (
                "parameter", "bitcast", "get-tuple-element", "tuple",
                "while", "copy-done"):
            found[op.group(1)] = found.get(op.group(1), 0) + 1
    return found


def _strip_payloads(text: str) -> str:
    """The HLO text with Mosaic payloads, the source tables (file and
    function names, locations, stack frames) and every instruction's
    ``metadata`` cut: what two checkouts' programs are compared by (an
    edit elsewhere in a file moves line numbers, not the program)."""
    out, skip = [], False
    for line in text.splitlines():
        if re.fullmatch(
                r"(FileNames|FunctionNames|FileLocations|StackFrames)\s*",
                line):
            skip = True
        elif skip:
            skip = bool(line.strip())
        else:
            out.append(line)
    text = "\n".join(out) + "\n"
    text = re.sub(r'backend_config="[^"]*"', 'backend_config="..."', text)
    text = re.sub(r"backend_config=\{[^\n]*", "backend_config={...}", text)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", text)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True,
                    help="a directory of chipbench/configs/")
    ap.add_argument("--tokens", type=int, default=512,
                    help="the mixed step's and the prefill's chunk rows")
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--programs", default="mixed,decode,prefill")
    ap.add_argument("--dump", default="")
    ap.add_argument("--time-lower", action="store_true",
                    help="print each program's trace + lower seconds "
                         "and compile nothing")
    ap.add_argument("--repeat", type=int, default=1,
                    help="--time-lower: readings a program (the least "
                         "is printed)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    jax.config.update("jax_enable_compilation_cache", False)
    cdir = ROOT / "chipbench" / "configs" / args.config
    flags = json.loads((cdir / "serve.json").read_text())["flags"]
    cfg = ModelConfig.from_local_path(str(cdir))
    b = int(_flag(flags, "--max-batch", 32))
    n = int(_flag(flags, "--num-blocks"))
    bs = 16
    m = int(_flag(flags, "--max-context", 4096)) // bs
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def shaped(tree):
        return jax.tree.map(lambda a: chip(a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0))))
    pool, pool_v = llama.kv_cache_shapes(cfg, n, bs)
    # (a latent cache's second pool is narrower: the rotary keys)
    cache, cache_v = chip(pool, jnp.bfloat16), chip(pool_v, jnp.bfloat16)
    tables = lambda *rows: chip(rows + (m,), jnp.int32)  # noqa: E731
    if cfg.window_kv_pool:
        # window and full layers in one model: a pair of caches (the
        # window pool's size is the engine's own derivation) and of tables
        from dynamo_tpu.engine.kv_manager import window_pool_blocks

        wn = window_pool_blocks(
            cfg, b, bs, int(_flag(flags, "--mixed-step-budget", 2048)), 2048,
            int(_flag(flags, "--window-blocks", 0)))
        cache, cache_v = shaped(jax.eval_shape(
            lambda: llama.init_kv_cache(cfg, n, bs, window_blocks=wn)))
        pool = cache[0].shape  # (the full pool: what a slab is counted in)
        print(f"{args.config}: window pool {list(cache[1].shape)} bf16 = "
              f"{cache[1].size * 2 / GIB:.3f} GiB a cache")
        tables = lambda *rows: (  # noqa: E731
            chip(rows + (m,), jnp.int32),) * 2
    slab_bytes = 2
    for d in pool[1:]:
        slab_bytes *= d
    kw = {}
    if cfg.is_moe:
        kw["moe_counters"] = True
    state, snap, snaps = None, {}, {}
    if cfg.state_layers:
        rows = int(_flag(flags, "--state-snapshots", 0))
        state = shaped(jax.eval_shape(
            lambda: llama.init_state(cfg, b, n, rows)))
        if cfg.linear_layers:  # snapshotted at a chunk's end, by row
            snap = {"snap_row": chip((), jnp.int32)}
            snaps = {"p_snaps": chip((1,), jnp.int32)}
    # the decode rows as the engine hands them since PR 48: the resident
    # matrix and a dispatch's cells (engine/step_state.py)
    from dynamo_tpu.engine.step_state import DELTA_CELLS

    n_tables = 2 if cfg.window_kv_pool else 1
    batch = llama.ROWS_RESIDENT
    rows = {"rows": chip((b, llama.ROW_TABLES + n_tables * m), jnp.int32),
            "rows_delta": chip((DELTA_CELLS, 3), jnp.int32)}
    one_i, scalar = chip((1,), jnp.int32), chip((), jnp.int32)
    lowered = {
        "mixed": lambda t: llama.mixed_step.lower(
            params, cfg, *batch, chip((1, t), jnp.int32),
            tables(1), one_i, one_i, cache, cache_v,
            use_pallas=True, **kw, **rows,
            **({"state": state, "p_slots": one_i, **snaps}
               if state else {})),
        "decode": lambda t: llama.decode_window.lower(
            params, cfg, *batch, cache, cache_v, n_steps=args.window,
            use_pallas=True, **kw, **rows,
            **({"state": state} if state else {})),
        "prefill": lambda t: llama.prefill.lower(
            params, cfg, chip((t,), jnp.int32), tables(),
            scalar, scalar, cache, cache_v, use_pallas=True, **kw,
            **({"state": state, "slot": scalar, **snap} if state else {})),
    }
    t = args.tokens
    if args.time_lower:
        names = args.programs.split(",")
        least = dict.fromkeys(names, float("inf"))
        for _ in range(args.repeat):
            jax.clear_caches()
            lowered["prefill"](16)  # thrown away: JAX's own start-up
            for name in names:
                t0 = time.thread_time()
                lowered[name](t)
                least[name] = min(least[name], time.thread_time() - t0)
        for name in names:
            print(f"{args.config} {name} "
                  f"({t if name != 'decode' else args.window}): trace + "
                  f"lower {least[name]:.3f} s")
        return 0
    print(f"{args.config}: pool {list(pool)} bf16 = "
          f"{slab_bytes * pool[0] / GIB:.3f} GiB a cache, one K slab "
          f"{slab_bytes / GIB:.4f} GiB")
    for name in args.programs.split(","):
        compiled = lowered[name](t).compile()
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        hits = _pool_sized(text, pool, pool_v)
        print(f"{name} ({t if name != 'decode' else args.window}): "
              f"arguments {mem.argument_size_in_bytes / GIB:.3f} GiB, "
              f"temporaries {mem.temp_size_in_bytes / GIB:.4f} GiB "
              f"({mem.temp_size_in_bytes / slab_bytes:.3f} of a slab), "
              f"custom calls {text.count('tpu_custom_call')}, "
              f"pool- or slab-sized results by opcode {hits}")
        if args.dump:
            out = Path(args.dump)
            out.mkdir(parents=True, exist_ok=True)
            (out / f"{args.config}.{name}.txt").write_text(
                _strip_payloads(text))
    return 0


if __name__ == "__main__":
    sys.exit(main())
