import jax
import jax.numpy as jnp

import flops_lm
import reference_lm

# a Moonlight-shaped cut at a small width: 1 dense + 1 MoE layer, 4 of 8
# experts held
CFG = {"hidden_size": 256, "num_attention_heads": 2,
       "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
       "kv_lora_rank": 64, "intermediate_size": 512,
       "moe_intermediate_size": 128, "n_shared_experts": 2,
       "router_outputs": 8, "n_routed_experts": 4,
       "experts_held": [0, 1, 2, 3], "num_hidden_layers": 2,
       "first_k_dense_replace": 1, "vocab_size": 1024,
       "num_experts_per_tok": 2, "routed_scaling_factor": 2.446,
       "rms_norm_eps": 1e-5, "rope_theta": 50000,
       "selection_bias_std": 0.05}


def test_flop_count_agrees_with_xla_for_one_local_step(monkeypatch):
    """flops_lm's model FLOPs of one step against XLA's count of the
    reference's gradient, recomputation off.  The reference runs every
    held expert on every token (``pairs`` = tokens x held) and scores
    every (query, key) pair before the causal mask, so both are added to
    what flops_lm counts for routed pairs and causal scores."""
    monkeypatch.setattr(jax, "checkpoint", lambda f, **_: f)
    seq = 64
    params = reference_lm.init_params(3, CFG)
    arch = reference_lm.arch_of(CFG)
    doc = jnp.zeros((seq + 1,), jnp.int32)

    def step(p):
        return jax.grad(lambda q: reference_lm.loss_sum(
            q, arch, doc[:-1], doc[1:])[0])(p)
    cost = jax.jit(step).lower(params).compile().cost_analysis()
    xla = cost["flops"] if isinstance(cost, dict) else cost[0]["flops"]
    m = flops_lm.dims(CFG)
    moe_layers = m["layers"] - m["dense_layers"]
    ours = flops_lm.step_flops(m, seq, seq * m["held"] * moe_layers)
    square = seq * seq - seq * (seq + 1) // 2
    ours += 6 * m["layers"] * square * m["h"] * (m["dn"] + m["dr"] + m["dv"])
    # XLA also counts the elementwise work (norms, rope, softmax, SwiGLU)
    assert 1.0 <= xla / ours <= 1.15, (xla, ours, xla / ours)


def test_round_and_expert_counts_scale_with_the_pairs():
    m = flops_lm.dims(CFG)
    per_pair = 6 * 3 * m["d"] * m["f"]
    assert flops_lm.round_flops(CFG, 10, 64, 1000) \
        - flops_lm.round_flops(CFG, 10, 64, 0) == 1000 * per_pair
    assert flops_lm.expert_flops(CFG, 1000) == 4 * 1000 * per_pair / 3
