"""Architecture ``pangu_ultra_moe``: a configuration file -> the program's
model (``deepspeed_tpu.models.pangu_ultra_moe``: multi-head latent attention,
sandwich norms, leading dense layers, then sigmoid-routed experts beside one
shared expert), its sharding rules, and the size dictionary the plain
reference reads.

**One chip's share** (``perfbench/README.md``).  Where the file has a
``share`` block, its ``n_routed_experts`` is the number of experts HELD, the
router keeps the published width (``published.n_routed_experts``), and the
first expert held is ``share.this_chip`` x held: the program's model gets
``n_routed_experts`` (the router's width), ``experts_held`` and
``first_expert``, the reference ``experts_held`` and ``first_expert`` (it
takes the router's width from the gate's own shape).  ``vocab_size`` is the
slice run.  The depth counts the leading dense layers
(``first_k_dense_replace``, never cut) and the routed layers after them.
"""

import jax
import jax.numpy as jnp

#: the keys the configuration file, PanguUltraMoeConfig and the reference
#: share (the published ``config.json``'s own names)
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "first_k_dense_replace",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor", "rms_norm_eps", "rope_theta")
#: what the program's config also carries or checks, and the reference has no
#: use for
PROGRAM_KEYS = ("num_key_value_heads", "max_position_embeddings",
                "sandwich_norm", "num_nextn_predict_layers",
                "attention_bias", "hidden_act", "tie_word_embeddings")


def depth_of(config, job):
    d = config["num_hidden_layers"]
    return int(d[job]) if isinstance(d, dict) else int(d)


def held_experts(config):
    """``(router width, experts held, first expert held)``."""
    held = int(config["n_routed_experts"])
    share = config.get("share")
    if not share:
        return held, held, 0
    return (int(config["published"]["n_routed_experts"]), held,
            int(share.get("this_chip", 0)) * held)


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary."""
    sizes = {k: config[k] for k in KEYS}
    _, held, first = held_experts(config)
    sizes.update(num_hidden_layers=depth_of(config, job), experts_held=held,
                 first_expert=first)
    return sizes


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import pangu_ultra_moe
    width, held, first = held_experts(config)
    fields = {k: config[k] for k in KEYS + PROGRAM_KEYS}
    fields.update(num_hidden_layers=depth_of(config, job),
                  n_routed_experts=width, experts_held=held,
                  first_expert=first)
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = pangu_ultra_moe.PanguUltraMoeConfig(**fields)
    return pangu_ultra_moe.PanguUltraMoeModel(cfg), \
        pangu_ultra_moe.tp_rules(cfg)


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
