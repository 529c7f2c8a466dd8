"""MNIST MLP inference endpoint: north-star config 2 (BASELINE.md).

Single model, dynamic batching, full framework plumbing: the handler enqueues
into the batcher and blocks on the future; the batcher pads to power-of-two
batches and runs one compiled XLA program.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402

from gofr_tpu import App  # noqa: E402
from gofr_tpu.http.errors import InvalidParam  # noqa: E402
from gofr_tpu.models.mlp import MLPConfig, mlp_forward, mlp_init  # noqa: E402
from gofr_tpu.tpu.device import TPUClient  # noqa: E402
from gofr_tpu.tpu.executor import Executor  # noqa: E402
from gofr_tpu.tpu.scheduler import DynamicBatcher  # noqa: E402


def main() -> None:
    os.chdir(os.path.dirname(os.path.abspath(__file__)))
    app = App()

    # TPU datasource via the provider pattern (externalDB.go:5-12 analog)
    tpu = TPUClient(app.config)
    app.add_tpu(tpu)

    cfg = MLPConfig()
    params = mlp_init(cfg, seed=0)
    executor = Executor(tpu)
    batcher = DynamicBatcher(lambda x: mlp_forward(params, x), executor=executor,
                             max_batch=app.config.get_int("MAX_BATCH", 64),
                             window_s=app.config.get_float("BATCH_WINDOW_S", 0.003),
                             name="mnist-mlp")
    batcher.start()
    # warm the common buckets so first requests don't pay compile latency
    import jax.numpy as jnp

    for b in (1, 8, 64):
        executor.warmup("mnist-mlp", lambda x: mlp_forward(params, x),
                        (jnp.zeros((b, cfg.in_dim)),))

    @app.post("/predict")
    def predict(ctx):
        body = ctx.bind()
        image = body.get("image")
        if not isinstance(image, list) or len(image) != cfg.in_dim:
            raise InvalidParam(["image"])
        logits = batcher.infer(np.asarray(image, dtype=np.float32),
                               timeout_s=ctx.remaining())
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        return {"digit": int(np.argmax(logits)),
                "probs": [round(float(p), 4) for p in probs]}

    app.run()


if __name__ == "__main__":
    main()
