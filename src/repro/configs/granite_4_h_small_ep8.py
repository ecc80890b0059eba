"""One chip's share of Granite-4.0-H-Small (``granite_4_h_small``).

The deployment: 40 layers as 4 pipeline stages of 10; in each stage 8 chips
share every layer by expert parallelism (9 of the 72 experts each), with
the Mamba-2, attention and shared-expert weights data-parallel. One chip
holds one whole period, layers 0-9 (9 Mamba-2 layers and 1 attention
layer), experts 0-8 of each layer, and the whole vocabulary. The router
keeps its 72 outputs and its top-10; the chip computes its own experts'
part of each layer. Parameters in bfloat16, as the checkpoint is served.
"""
import dataclasses

from .granite_4_h_small import CONFIG as _FULL
from .granite_4_h_small import SMOKE as _FULL_SMOKE

CONFIG = dataclasses.replace(_FULL, name="granite_4_h_small_ep8",
                             n_layers=10, moe_held=(0, 9),
                             param_dtype="bfloat16")

SMOKE = dataclasses.replace(_FULL_SMOKE, name="granite_ep8_smoke",
                            moe_held=(0, 2))
