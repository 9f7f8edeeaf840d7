"""The jit names the trace readers look up are the names the program's
programs compile under: a rename fails here instead of leaving a metric
silently empty."""
import numpy as np

from benchmark.metrics import epoch_program_ms, epoch_program_roofline

LANES = 64


def module_name(jitted, *shapes) -> str:
    return jitted.lower(*shapes).compiler_ir("hlo").as_hlo_module().name


def test_fused_epoch_program_name():
    import jax

    from consensus_specs_tpu.ops import merkle_resident

    i64 = jax.ShapeDtypeStruct((LANES,), np.int64)
    flag = jax.ShapeDtypeStruct((LANES,), np.bool_)
    name = module_name(merkle_resident._jit_fused, i64, i64, flag, flag, flag, flag, i64, i64,
                       jax.ShapeDtypeStruct((9,), np.int64))
    assert name == epoch_program_ms.PROGRAM == epoch_program_roofline.PROGRAM


def test_balances_reduction_name():
    import jax

    from consensus_specs_tpu.ops import merkle_resident

    u32 = jax.ShapeDtypeStruct((LANES,), np.uint32)
    assert module_name(merkle_resident._jit_reduce, u32, u32) == "jit__reduce_to_root"
