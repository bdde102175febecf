"""moonlight-16b-a3b [mla_moe] — DeepSeek-V3 block: MLA, 64 sigmoid-routed experts top-6 beside 2 shared, first layer dense [hf:moonshotai/Moonlight-16B-A3B]."""
import dataclasses
from ..models.spec import MlaSpec, ModelSpec, RoutedMoeSpec

SPEC = ModelSpec(
    name="moonlight-16b-a3b", family="mla_moe", num_layers=27, d_model=2048,
    num_heads=16, num_kv_heads=16, d_ff=11264, vocab_size=163840,
    mla=MlaSpec(kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128),
    routed=RoutedMoeSpec(num_experts=64, top_k=6, d_expert=1408, num_shared=2,
                         routed_scale=2.446, norm_topk=True),
    first_dense=1, rope_theta=50000.0, norm_eps=1e-5,
    source="hf:moonshotai/Moonlight-16B-A3B",
)

REDUCED = dataclasses.replace(
    SPEC, num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
    vocab_size=512, mla=MlaSpec(kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16),
    routed=dataclasses.replace(SPEC.routed, num_experts=8, top_k=2, d_expert=32),
)
