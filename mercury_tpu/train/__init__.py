from mercury_tpu.train.checkpoint import (  # noqa: F401
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from mercury_tpu.train.state import MercuryState, create_state, make_optimizer  # noqa: F401
from mercury_tpu.train.step import make_train_step  # noqa: F401
from mercury_tpu.train.trainer import Trainer, build_dataset  # noqa: F401
