"""Real-weights ingestion: safetensors round-trip, streaming load, int8.

The r4 verdict's Missing #1: every served model was a random tree because
no weights-on-disk import path existed. These tests synthesize HF-layout
checkpoints with the module's own writer, then prove the loader boots a
model that is logits-EXACT vs the from-memory oracle — float, int8
quantize-on-load, tied embeddings, sharded index files, and the engine
end-to-end (greedy tokens identical from disk vs from memory).
"""

import json
import os
import struct

import numpy as np
import pytest

from gofr_tpu.models.llama import (LlamaConfig, llama_init, llama_prefill,
                                   init_kv_cache, quantize_weights)
from gofr_tpu.models.weights import (CheckpointReader, SafetensorsFile,
                                     export_llama_safetensors,
                                     load_llama_safetensors,
                                     write_safetensors)

CFG = LlamaConfig.debug()


def _logits(params, cfg, tokens):
    k, v = init_kv_cache(cfg, tokens.shape[0], tokens.shape[1])
    out, _, _ = llama_prefill(params, cfg, tokens, k, v)
    return np.asarray(out)


def _tokens(cfg, batch=2, t=16, seed=3):
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp

    return jnp.asarray(rng.integers(1, cfg.vocab_size, size=(batch, t)),
                       dtype=jnp.int32)


# ---------------------------------------------------------------------------
# container format
# ---------------------------------------------------------------------------

def test_safetensors_roundtrip_dtypes(tmp_path):
    import ml_dtypes

    path = str(tmp_path / "t.safetensors")
    tensors = {
        "f32": np.arange(12, dtype=np.float32).reshape(3, 4),
        "f16": np.linspace(-2, 2, 8, dtype=np.float16),
        "bf16": np.linspace(-1, 1, 6).astype(ml_dtypes.bfloat16).reshape(2, 3),
        "i8": np.arange(-5, 5, dtype=np.int8),
        "i64": np.array([2**40, -7], dtype=np.int64),
        "scalar": np.float32(7.5).reshape(()),
    }
    write_safetensors(path, tensors, metadata={"format": "pt"})
    f = SafetensorsFile(path)
    assert f.metadata == {"format": "pt"}
    assert set(f.keys()) == set(tensors)
    for name, want in tensors.items():
        got = f.tensor(name)
        assert got.dtype == want.dtype, name
        assert got.shape == want.shape, name
        np.testing.assert_array_equal(np.asarray(got, np.float64),
                                      np.asarray(want, np.float64))


def test_safetensors_header_is_standard(tmp_path):
    """Byte-level check against the published container layout: 8-byte LE
    length, JSON header, offsets relative to the data section."""
    path = str(tmp_path / "t.safetensors")
    write_safetensors(path, {"a": np.zeros((2, 2), np.float32)})
    raw = open(path, "rb").read()
    (hlen,) = struct.unpack("<Q", raw[:8])
    header = json.loads(raw[8:8 + hlen])
    assert header["a"]["dtype"] == "F32"
    assert header["a"]["shape"] == [2, 2]
    assert header["a"]["data_offsets"] == [0, 16]
    assert len(raw) == 8 + hlen + 16


def test_reader_rejects_corrupt_range(tmp_path):
    path = str(tmp_path / "t.safetensors")
    write_safetensors(path, {"a": np.zeros(4, np.float32)})
    raw = bytearray(open(path, "rb").read())
    (hlen,) = struct.unpack("<Q", raw[:8])
    header = json.loads(raw[8:8 + hlen])
    header["a"]["shape"] = [8]  # lies about the shape
    hb = json.dumps(header).encode()
    with open(path, "wb") as fp:
        fp.write(struct.pack("<Q", len(hb)))
        fp.write(hb)
        fp.write(raw[8 + hlen:])
    f = SafetensorsFile(path)
    with pytest.raises(ValueError, match="byte range"):
        f.tensor("a")


# ---------------------------------------------------------------------------
# HF-layout llama loading
# ---------------------------------------------------------------------------

def test_load_float_logits_exact(tmp_path):
    params = llama_init(CFG, seed=0)
    path = str(tmp_path / "model.safetensors")
    export_llama_safetensors(params, path)
    loaded = load_llama_safetensors(CFG, path)
    toks = _tokens(CFG)
    np.testing.assert_array_equal(_logits(params, CFG, toks),
                                  _logits(loaded, CFG, toks))


def test_load_directory_form(tmp_path):
    params = llama_init(CFG, seed=1)
    export_llama_safetensors(params, str(tmp_path / "model.safetensors"))
    loaded = load_llama_safetensors(CFG, str(tmp_path))
    toks = _tokens(CFG)
    np.testing.assert_array_equal(_logits(params, CFG, toks),
                                  _logits(loaded, CFG, toks))


def test_load_int8_matches_quantize_weights(tmp_path):
    """Quantize-on-load == init-then-quantize, leaf for leaf and in logits."""
    path = str(tmp_path / "model.safetensors")
    export_llama_safetensors(llama_init(CFG, seed=2), path)
    loaded8 = load_llama_safetensors(CFG, path, weight_dtype="int8")
    oracle8 = quantize_weights(llama_init(CFG, seed=2))
    assert loaded8["lm_head"].dtype == np.int8
    assert loaded8["layers"]["wq"].dtype == np.int8
    np.testing.assert_array_equal(np.asarray(loaded8["layers"]["wq"]),
                                  np.asarray(oracle8["layers"]["wq"]))
    np.testing.assert_array_equal(np.asarray(loaded8["tok_emb_s"]),
                                  np.asarray(oracle8["tok_emb_s"]))
    toks = _tokens(CFG)
    np.testing.assert_array_equal(_logits(oracle8, CFG, toks),
                                  _logits(loaded8, CFG, toks))


def test_tied_embeddings(tmp_path):
    """No lm_head.weight in the file -> lm_head = tok_emb.T (Llama-3.2-1B
    ships tied)."""
    params = llama_init(CFG, seed=4)
    path = str(tmp_path / "model.safetensors")
    export_llama_safetensors(params, path)
    # rewrite without the head tensor
    f = SafetensorsFile(path)
    tensors = {n: f.tensor(n) for n in f.keys() if n != "lm_head.weight"}
    write_safetensors(path, tensors)
    loaded = load_llama_safetensors(CFG, path)
    np.testing.assert_array_equal(np.asarray(loaded["lm_head"]),
                                  np.asarray(loaded["tok_emb"]).T)


def test_sharded_index_checkpoint(tmp_path):
    """HF multi-shard layout: weight_map in model.safetensors.index.json."""
    params = llama_init(CFG, seed=5)
    whole = str(tmp_path / "whole.safetensors")
    export_llama_safetensors(params, whole)
    f = SafetensorsFile(whole)
    names = sorted(f.keys())
    half = len(names) // 2
    shards = {"model-00001-of-00002.safetensors": names[:half],
              "model-00002-of-00002.safetensors": names[half:]}
    weight_map = {}
    for fname, members in shards.items():
        write_safetensors(str(tmp_path / fname),
                          {n: f.tensor(n) for n in members})
        weight_map.update({n: fname for n in members})
    with open(tmp_path / "model.safetensors.index.json", "w") as fp:
        json.dump({"weight_map": weight_map}, fp)
    os.remove(whole)
    loaded = load_llama_safetensors(CFG, str(tmp_path))
    toks = _tokens(CFG)
    np.testing.assert_array_equal(_logits(params, CFG, toks),
                                  _logits(loaded, CFG, toks))


def test_config_mismatch_fails_fast(tmp_path):
    import dataclasses

    path = str(tmp_path / "model.safetensors")
    export_llama_safetensors(llama_init(CFG, seed=6), path)
    wrong = dataclasses.replace(CFG, ffn_dim=CFG.ffn_dim * 2)
    with pytest.raises(ValueError, match="does not match config"):
        load_llama_safetensors(wrong, path)


def test_missing_tensor_named_in_error(tmp_path):
    path = str(tmp_path / "model.safetensors")
    export_llama_safetensors(llama_init(CFG, seed=7), path)
    f = SafetensorsFile(path)
    tensors = {n: f.tensor(n) for n in f.keys()
               if n != "model.layers.1.mlp.up_proj.weight"}
    write_safetensors(path, tensors)
    with pytest.raises(ValueError, match="up_proj"):
        load_llama_safetensors(CFG, path)


def test_export_rejects_quantized_tree(tmp_path):
    q = quantize_weights(llama_init(CFG, seed=8))
    with pytest.raises(ValueError, match="float trees only"):
        export_llama_safetensors(q, str(tmp_path / "x.safetensors"))


# ---------------------------------------------------------------------------
# engine end-to-end from disk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("weight_dtype", [None, "int8"])
def test_engine_boots_from_disk_token_parity(tmp_path, weight_dtype):
    """The serving engine fed from disk generates the SAME tokens as the
    engine fed the in-memory tree (greedy, so parity is exact)."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    path = str(tmp_path / "model.safetensors")
    export_llama_safetensors(llama_init(CFG, seed=9), path)
    loaded = load_llama_safetensors(CFG, path, weight_dtype=weight_dtype)
    oracle_params = (quantize_weights(llama_init(CFG, seed=9))
                     if weight_dtype == "int8" else llama_init(CFG, seed=9))

    prompts = [[5, 6, 7, 8], [9, 10, 11, 12, 13, 14]]
    outs = []
    for params in (oracle_params, loaded):
        eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                             prefill_buckets=(8,))
        eng.start()
        try:
            handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
            outs.append([h.result(timeout_s=120) for h in handles])
        finally:
            eng.stop()
    assert outs[0] == outs[1]
