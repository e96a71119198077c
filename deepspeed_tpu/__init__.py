"""deepspeed_tpu — a TPU-native framework with DeepSpeed's capabilities.

Brand-new design (not a port): JAX/XLA/pjit/Pallas compute path over a global
``jax.sharding.Mesh``; ZeRO = sharding policies; comm = mesh collectives.
Public API mirrors the reference's ``deepspeed/__init__.py`` surface
(``initialize`` at reference ``deepspeed/__init__.py:69``, ``init_inference``
at ``:291``, ``add_config_arguments`` at ``:268``).
"""

__version__ = "0.5.0"   # keep in sync with version.txt (setup.py reads it)
# __git_branch__/git_hash/git_branch resolve lazily from the checkout (see
# __getattr__); "unknown" outside a git checkout
__git_branch__ = "unknown"

from . import comm
from . import utils
from .accelerator import get_accelerator
from .utils.logging import logger, log_dist

dist = comm


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port=29500,
               mesh_param=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               mpu=None,
               config_params=None,
               tp_rules=None):
    """Build the training engine.

    Reference ``deepspeed/__init__.py:69``.  Returns
    ``(engine, optimizer, training_dataloader, lr_scheduler)``.

    TPU-native signature differences:
      * ``model`` is a flax ``nn.Module``, haiku transform, or a plain apply
        callable ``f(params, batch, rngs) -> output``;
      * ``model_parameters`` is the parameter pytree (or ``None`` to let the
        engine initialize from ``model.init``);
      * ``mpu``/``mesh_param`` configure the (pp, dp, sp, tp) mesh factoring.
    """
    from .runtime.engine import DeepSpeedEngine
    from .runtime.config import DeepSpeedConfig
    from .runtime.pipe.module import PipelineModule

    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)

    ds_config = DeepSpeedConfig(config, mesh_param=mesh_param)

    _offload_param_dev = (str(ds_config.zero_config.offload_param.device)
                          if ds_config.zero_config.offload_param is not None
                          else "none")
    if isinstance(model, PipelineModule):
        if _offload_param_dev in ("cpu", "nvme"):
            raise ValueError(
                "offload_param (ZeRO-Infinity param streaming) does not "
                "compose with PipelineModule — the fused pipeline program "
                "needs its stage weights resident; use offload_optimizer "
                "for state offload under pipeline parallelism")
        from .runtime.pipe.engine import PipelineEngine  # noqa
        engine = PipelineEngine(args=args,
                                model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                collate_fn=collate_fn,
                                config=ds_config,
                                mpu=mpu)
    elif _offload_param_dev in ("cpu", "nvme"):
        # ZeRO-Infinity param streaming (reference engine choice: stage-3
        # offload_param routes through DeepSpeedZeroOptimizer_Stage3 +
        # AsyncPartitionedParameterSwapper)
        from .runtime.infinity_engine import InfinityEngine
        engine = InfinityEngine(args=args,
                                model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                collate_fn=collate_fn,
                                config=ds_config,
                                mpu=mpu,
                                tp_rules=tp_rules)
    elif ds_config.hybrid_engine.enabled:
        # RLHF flip-flop engine (reference engine choice deepspeed/__init__.py:214)
        from .runtime.hybrid_engine import DeepSpeedHybridEngine
        engine = DeepSpeedHybridEngine(args=args,
                                       model=model,
                                       optimizer=optimizer,
                                       model_parameters=model_parameters,
                                       training_data=training_data,
                                       lr_scheduler=lr_scheduler,
                                       collate_fn=collate_fn,
                                       config=ds_config,
                                       mpu=mpu,
                                       tp_rules=tp_rules)
    else:
        engine = DeepSpeedEngine(args=args,
                                 model=model,
                                 optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler,
                                 collate_fn=collate_fn,
                                 config=ds_config,
                                 mpu=mpu,
                                 tp_rules=tp_rules)

    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model, config=None, **kwargs):
    """Reference ``deepspeed/__init__.py:291``."""
    from .inference.engine import InferenceEngine
    from .inference.config import DeepSpeedInferenceConfig
    if config is None:
        config = {}
    if isinstance(config, DeepSpeedInferenceConfig):
        if kwargs:
            # merge explicit kwargs over the config object (reference
            # init_inference rejects double-specification; we apply overrides)
            merged = config.model_dump()
            merged.update(kwargs)
            config = DeepSpeedInferenceConfig(**merged)
        ds_inference_config = config
    else:
        config = dict(config)
        config.update(kwargs)
        ds_inference_config = DeepSpeedInferenceConfig(**config)
    return InferenceEngine(model, config=ds_inference_config)


def add_config_arguments(parser):
    """Reference ``deepspeed/__init__.py:268`` — argparse plumbing."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag for config)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse
    return argparse.SUPPRESS


def default_inference_config():
    """Default DeepSpeedInferenceConfig as a dict (reference
    ``deepspeed/__init__.py:284``)."""
    from .inference.config import DeepSpeedInferenceConfig
    return DeepSpeedInferenceConfig().model_dump()


def is_compile_supported():
    """Reference ``runtime/compiler.py`` — torch.compile availability.  On
    TPU every engine step is already XLA-compiled; always True."""
    return True


# lazy conveniences mirroring the reference's top-level namespace
def __getattr__(name):
    if name == "OnDevice":
        from .utils.init_on_device import OnDevice
        return OnDevice
    if name in ("DeepSpeedTransformerLayer", "DeepSpeedTransformerConfig"):
        from .ops import transformer
        return getattr(transformer, name)
    if name in ("PipelineModule", "LayerSpec", "TiedLayerSpec"):
        from .runtime import pipe
        return getattr(pipe, name)
    if name == "DeepSpeedEngine":
        from .runtime.engine import DeepSpeedEngine
        return DeepSpeedEngine
    if name == "InferenceEngine":
        from .inference.engine import InferenceEngine
        return InferenceEngine
    if name == "DeepSpeedConfig":
        from .runtime.config import DeepSpeedConfig
        return DeepSpeedConfig
    if name in ("replace_transformer_layer", "revert_transformer_layer"):
        from . import module_inject
        return getattr(module_inject, name)
    if name == "zero":
        from .runtime import zero
        return zero
    if name == "init_distributed":
        # reference deepspeed.init_distributed (deepspeed/__init__.py)
        return comm.init_distributed
    if name in ("add_tuning_arguments", "get_config_from_args"):
        from .runtime import lr_schedules
        return getattr(lr_schedules, name)
    if name == "checkpointing":
        # reference deepspeed.checkpointing module alias
        from .runtime.activation_checkpointing import checkpointing
        return checkpointing
    if name == "ops":
        # NOT `from . import ops`: inside the package's own __getattr__
        # that spelling re-enters this function before sys.modules is
        # populated and recurses
        import importlib
        return importlib.import_module(".ops", __name__)
    if name in ("git_hash", "git_branch"):
        # reference bakes these at build; derive lazily from the checkout
        # and memoize (PEP 562: the globals() write makes later accesses
        # bypass __getattr__ — no subprocess per read)
        import os as _os
        import subprocess
        root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        out = {"git_hash": "unknown", "git_branch": "unknown"}
        if _os.path.isdir(_os.path.join(root, ".git")):
            # only trust git when THIS checkout is the repo — a
            # pip-installed copy inside someone else's repository must not
            # report their HEAD
            for key, arg in (("git_hash", ("rev-parse", "--short", "HEAD")),
                             ("git_branch",
                              ("rev-parse", "--abbrev-ref", "HEAD"))):
                try:
                    out[key] = subprocess.check_output(
                        ("git", "-C", root) + arg, text=True,
                        stderr=subprocess.DEVNULL).strip()
                except Exception:
                    pass
        globals().update(out)
        globals()["__git_branch__"] = out["git_branch"]
        return out[name]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
