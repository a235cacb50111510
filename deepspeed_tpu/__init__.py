"""deepspeed_tpu — TPU-native distributed training & inference framework.

Brand-new JAX/XLA/Pallas implementation of the capabilities of the
reference DeepSpeed fork (mauryaavinash95/DeepSpeed v0.13.3 +
VELOC/DataStates async checkpointing). Public surface mirrors the
reference's ``deepspeed/__init__.py``: ``initialize`` (:69),
``init_distributed`` (:42), ``add_config_arguments`` (:245).
"""

__version__ = "0.1.0"

# DSTPU_COMM_OVERLAP=1: apply the comm-overlap XLA flag set (latency-
# hiding scheduler + async collectives; runtime/zero/overlap.py) NOW,
# before anything can initialize the backend — the only reliable point
# for launcher/bench subprocesses. No-op without the env var.
from .runtime.zero import overlap as _overlap
_overlap.apply_env_overlap_flags()

from . import comm
from .accelerator import get_accelerator
from .comm import init_distributed
from .runtime.config import DeepSpeedConfig
from .runtime.engine import DeepSpeedEngine
from .utils import groups, logger


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, topology=None,
               config=None, config_params=None, seed=0,
               dist_init_required=None):
    """Initialize the engine (reference deepspeed/__init__.py:69).

    Returns the reference's 4-tuple ``(engine, optimizer, dataloader,
    lr_scheduler)``. ``model`` is a functional model object
    (``init(rng) -> params``, ``loss(params, batch, rng=, train=)``,
    ``partition_specs(topology)``) — see ``deepspeed_tpu.models``.
    """
    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if config is None:
        raise ValueError("deepspeed_tpu.initialize needs a config "
                         "(dict or json path)")
    if dist_init_required is None or dist_init_required:
        init_distributed()

    engine = DeepSpeedEngine(model=model, config=config, optimizer=optimizer,
                             lr_scheduler=lr_scheduler, topology=topology,
                             seed=seed)

    dataloader = None
    if training_data is not None:
        from .runtime.dataloader import DeepSpeedDataLoader
        dataloader = DeepSpeedDataLoader(
            training_data, batch_size=engine.config.train_batch_size)
    return engine, engine.optimizer, dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Build an InferenceEngine (reference deepspeed/__init__.py:268).

    ``deepspeed_tpu.init_inference(model, tensor_parallel={"tp_size": 2},
    dtype="bfloat16")`` — TP sharding comes from the model's declarative
    ``partition_specs`` (the module_inject/AutoTP equivalent)."""
    from .inference.engine import InferenceEngine
    return InferenceEngine(model, config=config, **kwargs)


def add_config_arguments(parser):
    """argparse passthrough (reference deepspeed/__init__.py:245)."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed-TPU json configuration file")
    group.add_argument("--local_rank", type=int, default=-1,
                       help="accepted for launcher compatibility")
    return parser
