"""moonlight-16b-a3b-ep8 [mla_moe] — one chip's share of an 8-way expert-parallel Moonlight-16B-A3B: the router scores all 64 experts, experts 0-7 are held here; everything else as published [hf:moonshotai/Moonlight-16B-A3B]."""
import dataclasses
from . import moonlight_16b_a3b as whole

# Per-unit remat: the v5e memory plan of two clients at 2048 tokens needs
# 16.26G of 15.75G without it and 13.36G with it (bench/rehearse_v5e.py).
SPEC = dataclasses.replace(
    whole.SPEC, name="moonlight-16b-a3b-ep8",
    routed=dataclasses.replace(whole.SPEC.routed, held=(0, 8)), remat=True,
)

# the same share of the reduced model: expert 0 of 8
REDUCED = dataclasses.replace(
    whole.REDUCED, name="moonlight-16b-a3b-ep8",
    routed=dataclasses.replace(whole.REDUCED.routed, held=(0, 1)), remat=True,
)
