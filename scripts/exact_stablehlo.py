#!/usr/bin/env python3
"""Hash the exact cells' serving programs as they are lowered FOR THE TPU, with
no chip: ``search_fused_ragged_read`` at every batch bucket on ``share131k``'s
and ``lme5m``'s arenas, and ``make_fused_sharded``'s exact program on a 2x2
mesh at ``lme20m-mesh4``'s size — 18 modules, Mosaic payload and its source
lines included. Two checkouts whose lines agree cannot differ on the device
in an exact cell (PRs 31 and 36 showed their exact cells unmoved this way).
Each line ends with the hash of the Mosaic kernels alone (``lz_select_scan``
as the chip's compiler reads it: the payload's module printed WITHOUT its
source locations, which name the callers' lines in ``core/state.py`` too):
where a PR changes what surrounds the kernel (PR 37: the request carrier and
its prologue) the first hash differs and the last has to agree. A checkout
from before PR 37 is lowered with its own request operands.

    ln -sfn <checkout> /tmp/co && python3 scripts/exact_stablehlo.py /tmp/co

The Mosaic payload carries the source files' PATHS, so two checkouts are
compared under ONE path: point the same symlink at each in turn, run this,
and diff the two outputs. Lowering only: nothing is compiled or run, and no
number here is a device number."""

from __future__ import annotations

import base64
import hashlib
import inspect
import json
import os
import re
import sys


def mosaic_hash(text: str):
    """``(count, sha256)`` of the Mosaic kernels in a lowered module's text:
    every ``tpu_custom_call``'s payload, printed WITHOUT its source
    locations (``tests/test_select_core_tpu.py`` holds the serving kernels
    to the hashes frozen there)."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def kernel_asm(config: str) -> str:
        body = json.loads(config.replace("\\22", '"'))[
            "custom_call_config"]["body"]
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            return ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                enable_debug_info=False)

    mosaic = re.findall(
        r'@tpu_custom_call\(.*?backend_config = "((?:[^"\\]|\\.)*)"', text)
    return len(mosaic), hashlib.sha256(
        "".join(map(kernel_asm, mosaic)).encode()).hexdigest()


def main(checkout: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, checkout)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from lazzaro_tpu.core import state as S
    from lazzaro_tpu.ops import pallas_topk as PT

    PT.on_tpu = lambda: True        # the TPU's vehicle: the Pallas kernel
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    d = 768

    def show(name, c, text):
        count, kernels = mosaic_hash(text)
        print(name, c, hashlib.sha256(text.encode()).hexdigest(), len(text),
              count, kernels)

    # PR 37: the request operands are ONE int32 carrier
    carrier = "requests" in inspect.signature(
        S.search_fused_ragged_read).parameters

    def requests(sds, c, pod=False):
        if carrier:
            from lazzaro_tpu.utils.batching import REQUEST_COLS
            return (sds((c, d + REQUEST_COLS), jnp.int32),)
        cols = (sds((c, d), jnp.float32), sds((c,), jnp.bool_),
                sds((c,), jnp.int32), sds((c,), jnp.bool_),
                sds((c,), jnp.int32))
        return cols + ((sds((c,), jnp.int32),) if pod else ()) + (
            sds((), jnp.float32),)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    for rows, cell in ((135_168, "share131k"), (5_001_216, "lme5m")):
        st = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda: S.init_arena(rows - 1, d, jnp.bfloat16)))
        for c in range(8, 65, 8):
            show(cell, c, S.search_fused_ragged_read.lower(
                st, sds((rows + 1,), jnp.int32), sds((8192,), jnp.int32),
                *requests(sds, c), k=128, cap_take=5, max_nbr=8).as_text())

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    n, edges = 4 * 1221 * 4096, 4096

    def ms(shape, dt, spec=None):
        spec = spec if spec is not None else P(*([None] * len(shape)))
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    st = jax.tree_util.tree_map(
        lambda a: ms(a.shape, a.dtype,
                     P("data", None) if a.ndim == 2 else P("data")),
        jax.eval_shape(lambda: S.init_arena(n - 1, d, jnp.bfloat16)))
    kern = S.make_fused_sharded(mesh, "data", k=128, cap_take=5, max_nbr=8,
                                mode="exact")
    for c in (8, 64):
        show("lme20m-mesh4", c, kern.read.lower(
            st, (), ms((4, n // 4 + 1), jnp.int32, P("data", None)),
            ms((4, edges), jnp.int32, P("data", None)),
            *requests(ms, c, pod=True)).as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else os.getcwd()))
