from commefficient_tpu.runtime.fed_model import (  # noqa: F401
    FedModel,
    drain_rounds,
    FedOptimizer,
    LambdaLR,
    TrainRun,
)
