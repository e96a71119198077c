"""Architecture ``cohere2_moe``: a configuration file -> the program's model
(``deepspeed_tpu.models.cohere2_moe``: a Cohere parallel block, sigmoid-routed
experts beside averaged shared experts, window and full attention layers
interleaved), its sharding rules, and the size dictionary the plain reference
reads.

**One chip's share** (``perfbench/README.md``).  Where the file has a
``share`` block, its ``num_experts`` is the number of experts HELD, the
router keeps the published width (``published.num_experts``), and the first
expert held is ``share.this_chip`` x held: the program's model gets
``num_experts`` (the router's width), ``experts_held`` and ``first_expert``,
the reference ``experts_held`` and ``first_expert`` (it takes the router's
width from the gate's own shape).  ``vocab_size`` is the slice run.  The
layer kinds are the first ``depth`` entries of ``layer_types``; the reference
gets them as one string, a letter a layer (``S`` sliding, ``F`` full).
"""

import jax
import jax.numpy as jnp

#: the keys the configuration file, Cohere2MoeConfig and the reference share
#: (the published ``config.json``'s own names)
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "layer_norm_eps", "rope_theta", "sliding_window",
        "num_experts_per_tok", "num_shared_experts", "norm_topk_prob",
        "logit_scale")
#: what the program's config also checks, and the reference has no use for
PROGRAM_KEYS = ("max_position_embeddings", "expert_selection_fn",
                "shared_expert_combination_strategy",
                "position_embedding_type", "use_parallel_block",
                "use_qk_norm", "attention_bias", "hidden_act",
                "tie_word_embeddings")
LETTER = {"sliding_attention": "S", "full_attention": "F"}


def depth_of(config, job):
    d = config["num_hidden_layers"]
    return int(d[job]) if isinstance(d, dict) else int(d)


def held_experts(config):
    """``(router width, experts held, first expert held)``."""
    held = int(config["num_experts"])
    share = config.get("share")
    if not share:
        return held, held, 0
    return (int(config["published"]["num_experts"]), held,
            int(share.get("this_chip", 0)) * held)


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary."""
    sizes = {k: config[k] for k in KEYS}
    depth = depth_of(config, job)
    _, held, first = held_experts(config)
    sizes.update(num_hidden_layers=depth, experts_held=held,
                 first_expert=first, layer_kinds="".join(
                     LETTER[t] for t in config["layer_types"][:depth]))
    return sizes


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import cohere2_moe
    depth = depth_of(config, job)
    width, held, first = held_experts(config)
    fields = {k: config[k] for k in KEYS + PROGRAM_KEYS}
    fields.update(num_hidden_layers=depth, num_experts=width,
                  experts_held=held, first_expert=first,
                  layer_types=tuple(config["layer_types"][:depth]))
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = cohere2_moe.Cohere2MoeConfig(**fields)
    return cohere2_moe.Cohere2MoeModel(cfg), cohere2_moe.tp_rules(cfg)


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
