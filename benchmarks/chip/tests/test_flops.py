import jax
import jax.numpy as jnp

import flops
import reference


def test_flop_count_agrees_with_xla_for_one_local_step():
    vocab, topics, hidden, batch = 2000, 50, (100, 100), 16
    params = reference.init_params(3, vocab, topics, hidden)
    bow = jnp.ones((batch, vocab), jnp.float32)

    def step(p, b):
        loss = lambda q: jnp.mean(reference.elbo(  # noqa: E731
            q, b, jax.random.PRNGKey(0), 0.2))
        return jax.grad(loss)(p)
    cost = jax.jit(step).lower(params, bow).compile().cost_analysis()
    xla = cost["flops"] if isinstance(cost, dict) else cost[0]["flops"]
    ours = batch * flops.train_flops_per_doc(vocab, topics, hidden)
    # XLA also counts the elementwise work (softplus, softmax, the KL,
    # the random draws), a few per cent at these widths
    assert 1.0 <= xla / ours <= 1.15, (xla, ours)


def test_round_flops_scale_with_the_cohort():
    cfg = {"vocab_size": 5000, "num_topics": 50, "hidden": [100, 100]}
    per_doc = flops.train_flops_per_doc(5000, 50, [100, 100])
    assert flops.round_flops(cfg, 128, 4, 64) == 128 * 4 * 64 * per_doc
    assert flops.macs_per_doc(5000, 50, [100, 100]) == 770_000
