"""Compile a round step for a described TPU ``v5e:2x2`` without a chip.

Nothing runs: the compiler refuses what the chip's compiler would refuse
(a kernel's tiling, its scoped VMEM, a program larger than HBM), and
``memory_analysis`` says what the step needs.  Used by ``rehearse.py``.
"""

from __future__ import annotations

import os


def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def placed(tree, sharding):
    """ShapeDtypeStructs of ``tree`` (arrays or shapes) on ``sharding``."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def report(compiled, chips: int) -> str:
    m = compiled.memory_analysis()
    kernel = "tpu_custom_call" in compiled.as_text()
    return (f"round step compiled for {chips} described chip(s); Pallas kernel in it: "
            f"{kernel}; per chip argument {m.argument_size_in_bytes} B, temp "
            f"{m.temp_size_in_bytes} B, output {m.output_size_in_bytes} B")
