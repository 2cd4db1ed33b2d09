from repro.kernels.vcgra.ops import (
    make_batched_fused_pallas_fn,
    make_batched_pallas_fn,
    pack_settings_batched,
    vcgra_apply,
    vcgra_apply_image,
)
from repro.kernels.vcgra.ref import vcgra_ref
from repro.kernels.vcgra.vcgra_kernel import (
    default_interpret,
    grid_steps,
    vcgra_batched,
    vcgra_fused_batched,
)

__all__ = [
    "default_interpret",
    "grid_steps",
    "make_batched_fused_pallas_fn",
    "make_batched_pallas_fn",
    "pack_settings_batched",
    "vcgra_apply",
    "vcgra_apply_image",
    "vcgra_batched",
    "vcgra_fused_batched",
    "vcgra_ref",
]
