"""Architecture ``qwen3_next``: a configuration file -> the program's model
(``deepspeed_tpu.models.qwen3_next``: three Gated DeltaNet layers in four
beside one gated softmax attention, softmax-routed experts beside a gated
shared expert in every layer, an untied head), its sharding rules, and the
size dictionary the plain reference reads.

**One chip's share** (``perfbench/README.md``).  Where the file has a
``share`` block, its ``num_experts`` is the number of experts HELD, the
router keeps the published width (``published.num_experts``), and the first
expert held is ``share.this_chip`` x held: the program's model gets
``num_experts`` (the router's width), ``experts_held`` and ``first_expert``,
the reference ``experts_held`` and ``first_expert`` (it takes the router's
width from the gate's own shape).  ``vocab_size`` is the slice run.  The
layer kinds follow from ``full_attention_interval`` and the depth.
"""

import jax
import jax.numpy as jnp

#: the keys the configuration file, Qwen3NextConfig and the reference share
#: (the published ``config.json``'s own names)
KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "partial_rotary_factor",
        "rope_theta", "full_attention_interval", "linear_conv_kernel_dim",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_num_key_heads", "linear_num_value_heads",
        "num_experts_per_tok", "moe_intermediate_size",
        "shared_expert_intermediate_size", "norm_topk_prob", "rms_norm_eps")
#: what the program's config also carries or checks, and the reference has no
#: use for
PROGRAM_KEYS = ("intermediate_size", "max_position_embeddings",
                "rope_scaling", "decoder_sparse_step", "hidden_act",
                "tie_word_embeddings", "use_sliding_window")


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
    _, held, first = held_experts(config)
    sizes.update(num_hidden_layers=depth_of(config, job), experts_held=held,
                 first_expert=first)
    return sizes


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import qwen3_next
    width, held, first = held_experts(config)
    fields = {k: config[k] for k in KEYS + PROGRAM_KEYS}
    fields.update(num_hidden_layers=depth_of(config, job), num_experts=width,
                  experts_held=held, first_expert=first,
                  mlp_only_layers=tuple(config["mlp_only_layers"]))
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = qwen3_next.Qwen3NextConfig(**fields)
    return qwen3_next.Qwen3NextModel(cfg), qwen3_next.tp_rules(cfg)


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
