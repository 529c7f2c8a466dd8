"""BERT `/embed` endpoint over gRPC + HTTP: north-star config 3 (BASELINE.md).

Dynamic batching with sequence-length buckets: each request enqueues its token
row; the batcher pads to (batch, seq) power-of-two buckets and runs one
compiled XLA program; masked mean-pooling makes the padding numerically
invisible (models/bert.py). The gRPC surface uses GenericService (grpcx) so
the same handler shape serves both transports.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402

from gofr_tpu import App  # noqa: E402
from gofr_tpu.http.errors import InvalidParam  # noqa: E402
from gofr_tpu.grpcx import GenericService  # noqa: E402
from gofr_tpu.models.bert import BertConfig, bert_embed, bert_init  # noqa: E402
from gofr_tpu.tpu.device import TPUClient  # noqa: E402
from gofr_tpu.tpu.executor import Executor  # noqa: E402
from gofr_tpu.tpu.scheduler import DynamicBatcher  # noqa: E402


def _encode(text: str, max_len: int) -> np.ndarray:
    # byte-level ids shifted by +1 so 0 stays the BERT pad id
    ids = [b + 1 for b in text.encode("utf-8")][: max_len]
    return np.asarray(ids or [1], dtype=np.int32)


def build_app(app: App = None) -> App:
    if app is None:
        app = App()
    tpu = TPUClient(app.config)
    app.add_tpu(tpu)

    preset = app.config.get_or_default("BERT_PRESET", "debug")
    cfg = BertConfig.base() if preset == "base" else BertConfig.debug()
    params = bert_init(cfg, seed=0)
    executor = Executor(tpu)
    seq_buckets = tuple(
        int(s) for s in app.config.get_or_default("SEQ_BUCKETS", "16,32,64,128").split(","))
    batcher = DynamicBatcher(
        lambda toks: bert_embed(params, cfg, toks), executor=executor,
        max_batch=app.config.get_int("MAX_BATCH", 32),
        window_s=app.config.get_float("BATCH_WINDOW_S", 0.003),
        seq_axis=0, seq_buckets=seq_buckets, pad_value=cfg.pad_id,
        name="bert-embed")
    batcher.start()
    app.batcher = batcher  # exposed for tests/shutdown

    max_len = min(cfg.max_seq_len, seq_buckets[-1])

    def embed(ctx):
        body = ctx.bind()
        if isinstance(body, dict) and "tokens" in body:
            try:
                tokens = np.asarray(body["tokens"], dtype=np.int32)
            except (ValueError, TypeError):
                raise InvalidParam(["tokens"])
            if tokens.ndim != 1 or tokens.size == 0 or tokens.size > max_len:
                raise InvalidParam(["tokens"])
            if (tokens < 1).any() or (tokens >= cfg.vocab_size).any():
                raise InvalidParam(["tokens"])
        elif isinstance(body, dict) and "text" in body:
            tokens = _encode(str(body["text"]), max_len)
        else:
            raise InvalidParam(["text"])
        vec = batcher.infer(tokens, timeout_s=ctx.remaining())
        return {"embedding": [round(float(v), 6) for v in vec],
                "dim": int(vec.shape[-1])}

    app.post("/embed", embed)
    app.register_grpc_service(GenericService("EmbedService", {"Embed": embed}))
    return app


def main() -> None:
    os.chdir(os.path.dirname(os.path.abspath(__file__)))
    app = build_app()
    app.run()


if __name__ == "__main__":
    main()
