"""Compare two checkouts' chunk programs, as compiled for a TPU v5e, with
what only names the source taken out.

    python tools/compare_chunk_hlo.py <checkout A> <checkout B> [cell ...]

Needs no chip: JAX runs on the CPU and the TPU compiler compiles for a
described v5e (one chip, or the 2x2 mesh of a four-chip cell).  For each
cell (default: every cell of B's ``BENCHMARK.json``) a child process per
checkout builds the cell's grid with that checkout's code
(``bench.harness.build_grid``), takes every Pallas choice the chip takes,
and compiles each chunk length the cell runs.  Each pair of texts is
compared twice:

* ``stripped``: without ``metadata={...}`` (``op_name`` paths, where
  ``jax.named_scope`` lands), the source-location tables, and the debug
  locations inside each Mosaic kernel's serialized body;
* ``renamed``: the same, with every ``%name`` renamed in order of first
  appearance (a few instruction names are made from the name stack).

Prints one line per program and exits non-zero where one differs.
"""
from __future__ import annotations

import difflib
import hashlib
import json
import os
import re
import subprocess
import sys
import tempfile

BODY = re.compile(r'"body":"([^"]*)"')
METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
NAME = re.compile(r"%[\w.\-]+")


def dump(root: str, cell: str, out: str) -> None:
    """Child: write ``<out>/<n>.txt`` for each chunk length of ``cell``."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(root, "src"), root]
    import pathlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"  # the chip's Pallas choices
    import repro.distrib.sharding as sharding

    sharding.mesh_platform = lambda mesh=None: "tpu"
    from bench import harness
    from repro.distrib.sharding import SWEEP_AXIS

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    grid = harness.build_grid(harness.load_cell(cell, pathlib.Path(root)), 1)
    eng, bucket = grid.engine, grid.engine.buckets[0]
    carry = eng.bucket_carry(bucket, grid.collect, grid.tel_spec)
    args = (carry, bucket.keys, bucket.scn, jnp.asarray(bucket.horizons),
            jnp.zeros((), jnp.int32))
    if eng.mesh is None:
        one = SingleDeviceSharding(topo.devices[0])
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            args)
    else:
        mesh = Mesh(np.asarray(topo.devices).reshape(eng.mesh.devices.shape),
                    eng.mesh.axis_names)
        eng.mesh = mesh

        def place(specs, tree):
            return jax.tree_util.tree_map(
                lambda s, sub: jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
                    sub),
                specs, tree, is_leaf=lambda s: isinstance(s, P))

        shapes = (place(eng._conn_state_specs(), carry),
                  place(P(SWEEP_AXIS), args[1]),
                  place(eng._conn_scn_specs(), args[2]),
                  place(P(SWEEP_AXIS), args[3]), place(P(), args[4]))
    for n in sorted(set(grid.sizes(bucket))):
        fn = eng._make_chunk_fn(bucket.program, n, grid.collect,
                                grid.tel_spec)
        text = fn.lower(*shapes).compile().as_text()
        pathlib.Path(out, f"{n}.txt").write_text(text)


def _kernel_asm(b64: str) -> str:
    """A Mosaic kernel's body printed without debug locations (hashed)."""
    import base64

    from jax._src.lib.mlir import ir
    from jax.experimental.pallas import tpu  # noqa: F401  (its dialect)

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(base64.b64decode(b64)).operation.get_asm(
            enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()


def stripped(text: str) -> str:
    head, _, body = text.partition("\n%")  # the tables precede the first %
    body = BODY.sub(lambda m: f'"body":"{_kernel_asm(m.group(1))}"',
                    "%" + body)
    return METADATA.sub("", head.splitlines()[0] + "\n" + body)


def renamed(text: str) -> str:
    names: dict = {}
    return NAME.sub(lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                    text)


def main(argv) -> int:
    if argv[:1] == ["--dump"]:
        dump(*argv[1:4])
        return 0
    a_root, b_root, *cells = argv
    with open(os.path.join(b_root, "BENCHMARK.json")) as f:
        chips = {w["name"]: w["chips"] for w in json.load(f)["workloads"]}
    differ = 0
    for cell in cells or list(chips):
        texts = []
        for root in (a_root, b_root):
            env = dict(os.environ, XLA_FLAGS=(
                f"--xla_force_host_platform_device_count={chips[cell]}"))
            with tempfile.TemporaryDirectory(prefix="chunk_hlo_") as out:
                subprocess.run([sys.executable, __file__, "--dump",
                                os.path.abspath(root), cell, out],
                               check=True, env=env)
                texts.append({f: open(os.path.join(out, f)).read()
                              for f in sorted(os.listdir(out))})
        for f in texts[1]:
            sa, sb = stripped(texts[0].get(f, "")), stripped(texts[1][f])
            same = sa == sb
            same_renamed = renamed(sa) == renamed(sb)
            print(f"{cell} chunk {f[:-4]}: stripped "
                  f"{'identical' if same else 'differs'}, renamed "
                  f"{'identical' if same_renamed else 'differs'}")
            if not same:
                for line in list(difflib.unified_diff(
                        sa.splitlines(), sb.splitlines(), lineterm="",
                        n=0))[2:12]:
                    print("   ", line[:200])
            differ += not same_renamed
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
