"""Pallas TPU kernels for the fusion tier (reference analog:
paddle/phi/kernels/fusion/*.cu).

``enabled()`` is the one gate the functional layer asks. It answers from
what the process can observe — the flag, the platform, the mesh — never
from a caught exception: where it says yes the kernel is used, and a Mosaic
lowering or compile error propagates to the caller. Interpret mode is
reachable only through the explicit ``interpret=True`` arguments the tests
pass.
"""
from ...base.flags import get_flag


def on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def enabled() -> bool:
    """True when fused ops should take their Pallas kernel: the flag is on,
    the default backend is a TPU, and the program is single-device. jax
    refuses to auto-partition a Mosaic custom call ("wrap the call in a
    shard_map"), so under a multi-device fleet mesh the XLA composition,
    which GSPMD can partition, is the path that runs."""
    if not (get_flag("use_pallas_kernels") and on_tpu()):
        return False
    from ...distributed import env

    mesh = env.instance().mesh
    return mesh is None or mesh.size == 1


from . import flash_attention, flashmask, rms_norm  # noqa: F401,E402
