"""One chip's share of a routed layer in the plain reference
(``perfbench/reference/mixtral.py``: ``sizes["experts_held"]`` /
``sizes["first_expert"]``), at tiny size on the CPU: a 16-expert, top-2 preset
built here from ``tiny_mixtral``, as two shares of 8.

What the README's section "One chip's share" says of the reference is held
here: the shares' partial routed outputs add up to the uncut layer's; a token
whose k-th and (k+1)-th experts are both held elsewhere reports an infinite
margin and gets no second answer; and what exchanging such a pair does to
this share's logits (nothing where the top-k weights are not renormalised, a
continuous change far under a held pair's where they are)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pb_helpers as pb
from perfbench import harness, loader, weights

serve = loader.load_part(pb.ROOT, "jobs", "serve")

EXPERTS, HELD, TOP_K, TOKENS = 16, 8, 2, 160
SEEDS = (0, 1, 2, 3_000_000_019)
_made = {}


def preset(seed):
    """``(reference, sizes, uncut weights, token ids)`` of the 16-expert
    preset; float32 weights, so that what is compared is arithmetic."""
    if seed not in _made:
        config, arch, ref = pb.parts("tiny_mixtral")
        config = dict(config, num_local_experts=EXPERTS,
                      num_experts_per_tok=TOP_K,
                      num_hidden_layers={"serve": 2})
        model, _ = arch.build(config, "serve")
        params = weights.seeded_weights(arch.param_shapes(model),
                                        harness.fold_seed(seed), jnp.float32)
        sizes = arch.reference_sizes(config, "serve")
        ids = np.random.default_rng([seed % 2**32, 9]).integers(
            0, sizes["vocab_size"], TOKENS)
        _made[seed] = ref, sizes, params, ids
    return _made[seed]


def share_of(params, sizes, chip):
    """The weights and sizes chip ``chip`` of ``EXPERTS // HELD`` holds: its
    experts' stacks, the router and everything else whole."""
    first = chip * HELD

    def cut(path, x):
        name = jax.tree_util.keystr(path)
        routed = "moe" in name and "gate" not in name
        return x[first:first + HELD] if routed else x
    return (jax.tree_util.tree_map_with_path(cut, params),
            dict(sizes, experts_held=HELD, first_expert=first))


def layer_by_layer(ref, params, sizes, ids):
    """The share's own forward, a layer at a time: ``[(router logits, margin
    as reported)]`` of each layer, on the hidden states THIS share sees."""
    x, out = ref.base.embed(params, jnp.asarray(ids, jnp.int32)), []
    with jax.default_matmul_precision(ref.HIGHEST):
        for i in range(sizes["num_hidden_layers"]):
            lp = params[f"layers_{i}"]
            x, router, margin, _ = ref.moe_block(
                ref.attention_block(x, lp, sizes), lp, sizes)
            out.append((np.asarray(router), np.asarray(margin)))
    return out


def kth_and_next(router):
    """Each token's k-th and (k+1)-th expert and the margin between them."""
    order = np.argsort(-router, axis=-1)
    rows = np.arange(len(router))
    kth, nxt = order[:, TOP_K - 1], order[:, TOP_K]
    return kth, nxt, router[rows, kth] - router[rows, nxt]


def near_ties(ref, params, sizes, ids, margin):
    """[tokens, layers]: the k-th and (k+1)-th router logits lie closer than
    ``margin``, wherever those two experts are held."""
    return np.stack([kth_and_next(router)[2] for router, _ in
                     layer_by_layer(ref, params, sizes, ids)], axis=1) < margin


def gap(logits, other):
    return float(jnp.max(jnp.abs(logits - other)) / jnp.std(logits))


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_shares_partial_outputs_add_up_to_the_uncut_layers(seed, layer):
    ref, sizes, params, _ = preset(seed)
    lp = params[f"layers_{layer}"]
    x = jax.random.normal(harness.fold_seed(seed), (TOKENS,
                                                    sizes["hidden_size"]))
    with jax.default_matmul_precision(ref.HIGHEST):
        whole = ref.moe_block(x, lp, sizes)[0] - x
        parts = []
        for chip in range(EXPERTS // HELD):
            p, s = share_of(params, sizes, chip)
            y, router, _, w = ref.moe_block(x, p[f"layers_{layer}"], s)
            parts.append(y - x)
            # the router keeps its width and its experts per token
            assert router.shape == w.shape == (TOKENS, EXPERTS)
            assert np.all(np.asarray((w > 0).sum(axis=1)) == TOP_K)
    scale = float(jnp.max(jnp.abs(whole)))
    assert scale > 0.1
    np.testing.assert_allclose(sum(parts), whole, atol=2e-6 * scale, rtol=0)
    # and neither part is the whole: each share left something out
    assert all(float(jnp.max(jnp.abs(p - whole))) > 0.05 * scale
               for p in parts)


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_every_expert_held_is_the_reference_with_no_share(seed):
    """No ``experts_held``: every expert, the bits it gave; and a share that
    holds all sixteen is the same computation."""
    ref, sizes, params, ids = preset(seed)
    at = np.arange(TOKENS)
    logits, margins = ref.logits_and_routing_at(params, ids, at, sizes)
    same, same_m = ref.logits_and_routing_at(
        params, ids, at, dict(sizes, experts_held=EXPERTS, first_expert=0))
    assert np.array_equal(np.asarray(logits), np.asarray(same))
    assert np.array_equal(np.asarray(margins), np.asarray(same_m))
    assert np.all(np.isfinite(np.asarray(margins)))
    dense = ref.logits_at(params, ids, at, sizes)
    np.testing.assert_allclose(dense, logits, atol=1e-5)


@pytest.mark.parametrize("chip", [0, 1])
@pytest.mark.parametrize("seed", SEEDS)
def test_margin_is_infinite_where_both_experts_are_held_elsewhere(seed, chip):
    ref, sizes, params, ids = preset(seed)
    p, s = share_of(params, sizes, chip)
    _, margins = ref.logits_and_routing_at(p, ids, np.arange(TOKENS), s)
    margins = np.asarray(margins)
    first = chip * HELD
    elsewhere = 0
    for i, (router, reported) in enumerate(layer_by_layer(ref, p, s, ids)):
        kth, nxt, margin = kth_and_next(router)
        here = ((kth >= first) & (kth < first + HELD)) | \
            ((nxt >= first) & (nxt < first + HELD))
        assert np.all(np.isinf(reported[~here]))
        np.testing.assert_allclose(reported[here], margin[here], atol=1e-5)
        # the jitted door reports the same (to rounding: it is compiled)
        assert np.array_equal(np.isinf(margins[:, i]), ~here)
        np.testing.assert_allclose(margins[here, i], margin[here], atol=1e-5)
        elsewhere += int((~here).sum())
    # 8 of 16 held: (8/16)(7/15) = 23 % of the pairs lie wholly elsewhere
    assert 0.12 < elsewhere / margins.size < 0.36


@pytest.mark.parametrize("renormalised", [False, True])
def test_exchanging_two_experts_held_elsewhere(renormalised):
    """Measured, not assumed (README, "One chip's share").  Top-k weights NOT
    renormalised: exchanging a pair held elsewhere leaves this share's logits
    bit for bit.  Renormalised over the k: the held experts' weights of that
    one token move by a factor between 1 and e^margin, a continuous change,
    which over the near-ties (margin under the preset's tolerance) stays
    under the gap tolerance and under the smallest change that exchanging a
    pair with an expert held HERE makes."""
    ctx = pb.serve_ctx("tiny_mixtral", "tiny_chat_routed")
    tols = serve.tolerances(ctx)
    at = np.arange(TOKENS)
    elsewhere, here = [], []
    for seed in SEEDS:
        ref, sizes, params, ids = preset(seed)
        p, s = share_of(params, sizes, 0)
        s = dict(s, norm_topk_prob=renormalised)
        logits, margins = ref.logits_and_routing_at(p, ids, at, s)
        held_pair = np.isfinite(np.asarray(margins))
        near = near_ties(ref, p, s, ids, tols["serve.router_margin"])
        for found, mask in ((elsewhere, near & ~held_pair),
                            (here, near & held_pair)):
            for t, layer in np.argwhere(mask)[:6]:
                flipped, _ = ref.logits_and_routing_at(
                    p, ids, at, s, flip=(int(layer), int(t)))
                assert np.array_equal(logits[:t], flipped[:t])   # causal
                found.append(gap(logits[t:], flipped[t:]))
    assert len(elsewhere) >= 12 and len(here) >= 12
    if renormalised:
        # PR 32, 12 seeds of this preset: 0 to 0.032, median 0.0032, against
        # 0.10 to 1.81 (median 0.63) for a pair with an expert held here
        assert max(elsewhere) < tols["serve.logit_gap"] < min(here)
        assert np.median(elsewhere) < 0.1 * tols["serve.logit_gap"]
    else:
        assert max(elsewhere) == 0.0 < min(here)


def test_second_answers_are_asked_only_where_an_expert_is_held_here():
    """``jobs/serve.py`` ``routed_logit_gaps``, unchanged, asks the REFERENCE
    for margins: under a share it tries fewer second answers than there are
    near-ties, by those whose pair lies wholly elsewhere."""
    ref, sizes, params, ids = preset(SEEDS[0])
    p, s = share_of(params, sizes, 1)
    margin = serve.tolerances(pb.serve_ctx("tiny_mixtral",
                                           "tiny_chat_routed"))[
        "serve.router_margin"]
    prompt, toks = ids[:40].tolist(), ids[40:72].tolist()
    row = serve.routed_logit_gaps(ref.logits_and_routing_at, p, s, [prompt],
                                  [toks], margin)[0]
    seen = ids[:71]
    near = near_ties(ref, p, s, seen, margin)
    _, margins = ref.logits_and_routing_at(p, seen, np.arange(len(seen)), s)
    held_pair = np.isfinite(np.asarray(margins))
    assert row[6] == (near & held_pair).sum() < near.sum()
    assert row[3] + row[5] == len(toks)
