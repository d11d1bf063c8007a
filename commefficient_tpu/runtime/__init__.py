from commefficient_tpu.runtime.fed_model import (  # noqa: F401
    FedModel,
    FedOptimizer,
    LambdaLR,
    TrainRun,
)
