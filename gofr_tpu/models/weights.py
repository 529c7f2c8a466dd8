"""Real-weights ingestion: safetensors -> llama params tree, streaming.

The serving stack (engine, paged engine, OpenAI surface) consumes the
params pytree produced by `models.llama.llama_init`; until this module the
only producers were random initializers, so "serve Llama-3-8B" was a claim
about an 8B-SHAPED model, never the model itself. This closes that gap with
a zero-dependency path from weights-on-disk to a bootable engine:

  read_safetensors / SafetensorsFile   pure-numpy reader for the standard
      safetensors container (8-byte LE header length + JSON header + raw
      little-endian tensor bytes). bf16 decodes through ml_dtypes (a jax
      dependency, always present). Multi-shard checkpoints resolve through
      the standard `*.safetensors.index.json` weight_map.
  write_safetensors                    the mirror writer — tests synthesize
      HF-layout checkpoints with it, and it gives deployments a way to
      persist converted/quantized trees.
  load_llama_safetensors               HF-layout names -> llama tree, ONE
      LEAF AT A TIME: each target leaf is assembled in host RAM, pushed to
      device, and (optionally) quantized to int8 on device before the next
      leaf is touched — the float tree never fully materializes on device,
      the same peak-HBM discipline as llama_init_quantized
      (models/llama.py:304-354). An 8B checkpoint loads into ~8.5 GiB of
      int8 leaves with one ~1 GiB float temp in flight.

Parity target: the reference boots services from versioned on-disk
artifacts rather than in-process state (migration watermark discipline,
/root/reference/pkg/gofr/migration/migration.go:18-79); here the artifact
is the model checkpoint and the version is the safetensors header itself
(shape+dtype validated leaf-by-leaf against the LlamaConfig before boot).

HF tensor layout (torch Linear stores [out, in]; our matmuls are x @ W with
W [in, out], so every projection transposes on load):

    model.embed_tokens.weight            [V, D]   -> tok_emb          [V, D]
    model.layers.{l}.self_attn.q_proj    [H*dh, D]-> layers.wq[l]     [D, H*dh]
    ...k_proj/v_proj                     [Hkv*dh,D]-> wk/wv[l]        [D, Hkv*dh]
    ...self_attn.o_proj                  [D, H*dh]-> wo[l]            [H*dh, D]
    ...mlp.gate_proj/up_proj             [F, D]   -> w_gate/w_up[l]   [D, F]
    ...mlp.down_proj                     [D, F]   -> w_down[l]        [F, D]
    ...input_layernorm.weight            [D]      -> layers.attn_norm[l]
    ...post_attention_layernorm.weight   [D]      -> layers.ffn_norm[l]
    model.norm.weight                    [D]      -> final_norm       [D]
    lm_head.weight                       [V, D]   -> lm_head          [D, V]
        (absent when embeddings are tied: lm_head = tok_emb.T)

HF Llama checkpoints use the rotate-half RoPE convention (q/k projections
pre-permuted by the HF conversion), which is exactly what models.llama.rope
computes — weights load with no head permutation.
"""

from __future__ import annotations

import json
import os
import struct
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

# safetensors dtype tag -> numpy dtype. BF16 has no numpy builtin; ml_dtypes
# (shipped with jax) provides a bit-exact one.
_DTYPES: Dict[str, Any] = {
    "F64": np.float64, "F32": np.float32, "F16": np.float16,
    "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
    "U8": np.uint8, "BOOL": np.bool_,
}


def _bf16():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def _np_dtype(tag: str):
    if tag == "BF16":
        return _bf16()
    try:
        return np.dtype(_DTYPES[tag])
    except KeyError:
        raise ValueError(f"unsupported safetensors dtype {tag!r}") from None


def _dtype_tag(dt: np.dtype) -> str:
    if dt == _bf16():
        return "BF16"
    for tag, npdt in _DTYPES.items():
        if np.dtype(npdt) == dt:
            return tag
    raise ValueError(f"cannot serialize dtype {dt} to safetensors")


class SafetensorsFile:
    """Lazy reader over one .safetensors container.

    Parses the header once; `tensor(name)` reads exactly that tensor's byte
    range (seek + frombuffer), so loading a 16 GiB checkpoint leaf-by-leaf
    never holds more than one tensor in memory beyond the OS page cache.
    """

    def __init__(self, path: str):
        self.path = path
        with open(path, "rb") as fp:
            (header_len,) = struct.unpack("<Q", fp.read(8))
            if header_len > 100 * 1024 * 1024:
                raise ValueError(f"{path}: implausible header size {header_len}")
            header = json.loads(fp.read(header_len).decode("utf-8"))
        self.metadata: Dict[str, str] = header.pop("__metadata__", {})
        self._entries: Dict[str, Tuple[str, Tuple[int, ...], int, int]] = {}
        data_start = 8 + header_len
        for name, ent in header.items():
            begin, end = ent["data_offsets"]
            self._entries[name] = (ent["dtype"], tuple(ent["shape"]),
                                   data_start + begin, data_start + end)

    def keys(self) -> Iterable[str]:
        return self._entries.keys()

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def info(self, name: str) -> Tuple[str, Tuple[int, ...]]:
        dtype, shape, _, _ = self._entries[name]
        return dtype, shape

    def tensor(self, name: str) -> np.ndarray:
        dtype, shape, begin, end = self._entries[name]
        npdt = _np_dtype(dtype)
        count = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = count * npdt.itemsize
        if nbytes != end - begin:
            raise ValueError(
                f"{self.path}:{name}: byte range {end - begin} != "
                f"shape/dtype size {nbytes}")
        with open(self.path, "rb") as fp:
            fp.seek(begin)
            buf = fp.read(nbytes)
        arr = np.frombuffer(buf, dtype=npdt, count=count).reshape(shape)
        return arr


class CheckpointReader:
    """Uniform view over a single file OR a sharded HF checkpoint directory.

    Accepts: a .safetensors file, a .safetensors.index.json file, or a
    directory containing either `model.safetensors` or
    `model.safetensors.index.json` (the HF hub layout).
    """

    def __init__(self, path: str):
        index_path = None
        if os.path.isdir(path):
            single = os.path.join(path, "model.safetensors")
            index = os.path.join(path, "model.safetensors.index.json")
            if os.path.exists(index):
                index_path = index
            elif os.path.exists(single):
                path = single
            else:
                sts = sorted(f for f in os.listdir(path)
                             if f.endswith(".safetensors"))
                if len(sts) == 1:
                    path = os.path.join(path, sts[0])
                else:
                    raise FileNotFoundError(
                        f"{path}: no model.safetensors[.index.json] "
                        f"({len(sts)} .safetensors files)")
        elif path.endswith(".index.json"):
            index_path = path

        self._files: Dict[str, SafetensorsFile] = {}
        self._where: Dict[str, str] = {}
        if index_path:
            base = os.path.dirname(index_path)
            with open(index_path, "r", encoding="utf-8") as fp:
                weight_map = json.load(fp)["weight_map"]
            for name, fname in weight_map.items():
                self._where[name] = os.path.join(base, fname)
        else:
            f = SafetensorsFile(path)
            self._files[path] = f
            for name in f.keys():
                self._where[name] = path

    def keys(self) -> Iterable[str]:
        return self._where.keys()

    def __contains__(self, name: str) -> bool:
        return name in self._where

    def _file(self, name: str) -> SafetensorsFile:
        path = self._where[name]
        if path not in self._files:
            self._files[path] = SafetensorsFile(path)
        return self._files[path]

    def info(self, name: str) -> Tuple[str, Tuple[int, ...]]:
        return self._file(name).info(name)

    def tensor(self, name: str) -> np.ndarray:
        return self._file(name).tensor(name)


def write_safetensors(path: str, tensors: Dict[str, np.ndarray],
                      metadata: Optional[Dict[str, str]] = None) -> None:
    """Serialize {name: numpy array} to one safetensors container.

    Arrays are written little-endian C-contiguous in sorted-name order
    (deterministic bytes for a given tree — artifact diffing stays honest).
    """
    header: Dict[str, Any] = {}
    if metadata:
        header["__metadata__"] = dict(metadata)
    blobs: List[bytes] = []
    offset = 0
    for name in sorted(tensors):
        # ascontiguousarray promotes 0-d to 1-d; reshape restores the
        # original shape (contiguity is preserved)
        arr = np.ascontiguousarray(tensors[name]).reshape(
            np.shape(tensors[name]))
        tag = _dtype_tag(arr.dtype)
        blob = arr.tobytes()
        header[name] = {"dtype": tag, "shape": list(arr.shape),
                        "data_offsets": [offset, offset + len(blob)]}
        blobs.append(blob)
        offset += len(blob)
    hbytes = json.dumps(header, separators=(",", ":")).encode("utf-8")
    tmp = path + ".tmp"
    with open(tmp, "wb") as fp:
        fp.write(struct.pack("<Q", len(hbytes)))
        fp.write(hbytes)
        for blob in blobs:
            fp.write(blob)
    os.replace(tmp, path)  # atomic publish, checkpoint.py's discipline


# ---------------------------------------------------------------------------
# HF-layout llama loading
# ---------------------------------------------------------------------------

# gofr stacked-leaf name -> (HF per-layer name, transpose?)
_LAYER_MAP = {
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "w_gate": ("mlp.gate_proj.weight", True),
    "w_up": ("mlp.up_proj.weight", True),
    "w_down": ("mlp.down_proj.weight", True),
    "attn_norm": ("input_layernorm.weight", False),
    "ffn_norm": ("post_attention_layernorm.weight", False),
}


def _expected_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    L, D, H, Hkv, dh, F, V = (cfg.n_layers, cfg.dim, cfg.n_heads,
                              cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
                              cfg.vocab_size)
    return {
        "tok_emb": (V, D),
        "wq": (L, D, H * dh), "wk": (L, D, Hkv * dh), "wv": (L, D, Hkv * dh),
        "wo": (L, H * dh, D),
        "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D),
        "attn_norm": (L, D), "ffn_norm": (L, D),
        "final_norm": (D,),
        "lm_head": (D, V),
    }


def _stack_layers(reader: CheckpointReader, cfg, leaf: str,
                  np_target) -> np.ndarray:
    hf_suffix, transpose = _LAYER_MAP[leaf]
    slices = []
    for l in range(cfg.n_layers):
        name = f"model.layers.{l}.{hf_suffix}"
        if name not in reader:
            raise KeyError(f"checkpoint missing tensor {name!r}")
        t = reader.tensor(name)
        slices.append(np.ascontiguousarray(t.T) if transpose else t)
    return np.stack(slices).astype(np_target, copy=False)


def load_llama_safetensors(cfg, path: str,
                           weight_dtype: Optional[str] = None,
                           logger=None) -> Dict[str, Any]:
    """Load an HF-layout Llama checkpoint into the serving params tree.

    cfg: LlamaConfig (shapes are VALIDATED against the checkpoint header
    before any bytes are read — a preset/checkpoint mismatch fails fast
    with the offending tensor named). weight_dtype: None keeps cfg.dtype
    storage; "int8" quantizes each leaf on device as it loads
    (per-output-channel scales, models.llama.quantize_leaf) so peak device
    memory is the int8 tree plus ONE float leaf.

    Returns the same pytree structure as llama_init / quantize_weights —
    every downstream consumer (engines, TP sharding via
    parallel.sharding.serving_param_specs, checkpoint.py) works unchanged.
    """
    import jax

    from .blocks import np_dtype as jax_dtype
    from .llama import QUANT_AXES, quantize_leaf

    reader = CheckpointReader(path)
    # jnp scalar types are numpy/ml_dtypes types — np.dtype() accepts both
    np_target = np.dtype(jax_dtype(cfg.dtype))
    tied = "lm_head.weight" not in reader

    # ---- preflight: every tensor present with the right shape ------------
    exp = _expected_shapes(cfg)
    problems: List[str] = []

    def check(hf_name: str, want: Tuple[int, ...]):
        if hf_name not in reader:
            problems.append(f"missing {hf_name}")
            return
        _, shape = reader.info(hf_name)
        if tuple(shape) != tuple(want):
            problems.append(f"{hf_name}: shape {shape} != expected {want}")

    check("model.embed_tokens.weight", exp["tok_emb"])
    check("model.norm.weight", exp["final_norm"])
    if not tied:
        check("lm_head.weight", (cfg.vocab_size, cfg.dim))
    for leaf, (suffix, transpose) in _LAYER_MAP.items():
        want = exp[leaf][1:]
        per_layer = tuple(reversed(want)) if transpose else want
        for l in range(cfg.n_layers):
            check(f"model.layers.{l}.{suffix}", per_layer)
    if problems:
        head = "; ".join(problems[:5])
        more = f" (+{len(problems) - 5} more)" if len(problems) > 5 else ""
        raise ValueError(f"checkpoint {path!r} does not match config: "
                         f"{head}{more}")

    if weight_dtype not in (None, "int8"):
        raise ValueError(f"weight_dtype must be int8 or None, "
                         f"got {weight_dtype!r}")
    quantize = weight_dtype == "int8"
    q = jax.jit(quantize_leaf, static_argnums=1) if quantize else None

    def log(msg, *args):
        if logger is not None:
            logger.debugf(msg, *args)

    def place(leaf_name: str, host: np.ndarray, quant_axis: Optional[int]):
        """Host array -> device leaf (optionally int8+scale), float temp
        freed before return (block_until_ready, llama_init_quantized's
        one-float-leaf-in-flight discipline)."""
        dev = jax.device_put(host)
        if quantize and quant_axis is not None:
            w8, s = q(dev, quant_axis)
            jax.block_until_ready(w8)
            del dev
            log("loaded %s int8 %s", leaf_name, w8.shape)
            return w8, s
        jax.block_until_ready(dev)
        log("loaded %s %s %s", leaf_name, dev.dtype, dev.shape)
        return dev, None

    params: Dict[str, Any] = {}
    layers: Dict[str, Any] = {}

    emb_host = reader.tensor("model.embed_tokens.weight").astype(
        np_target, copy=False)
    emb, emb_s = place("tok_emb", emb_host, -1 if quantize else None)
    params["tok_emb"] = emb
    if emb_s is not None:
        params["tok_emb_s"] = emb_s
    if not tied:
        # only the tied branch reuses the host embedding for lm_head; drop
        # it now so peak host RAM stays one large array during layer loads
        del emb_host

    for leaf in _LAYER_MAP:
        host = _stack_layers(reader, cfg, leaf, np_target)
        axis = QUANT_AXES.get(leaf)
        dev, s = place(f"layers.{leaf}", host, axis)
        del host
        layers[leaf] = dev
        if s is not None:
            layers[leaf + "_s"] = s
    params["layers"] = layers

    params["final_norm"] = jax.device_put(
        reader.tensor("model.norm.weight").astype(np_target, copy=False))

    if tied:
        head_host = np.ascontiguousarray(emb_host.T)
        del emb_host
    else:
        head_host = np.ascontiguousarray(
            reader.tensor("lm_head.weight").astype(np_target, copy=False).T)
    head, head_s = place("lm_head", head_host, -2 if quantize else None)
    params["lm_head"] = head
    if head_s is not None:
        params["lm_head_s"] = head_s
    return params


def export_llama_safetensors(params, path: str,
                             metadata: Optional[Dict[str, str]] = None) -> None:
    """Write a (float) llama params tree back out in HF layout.

    The inverse of load_llama_safetensors for float trees — tests round-trip
    through it, and it converts random-init trees into on-disk fixtures.
    Rejects int8 trees: HF layout has no scale-tensor convention, and an
    int8 tree should persist via checkpoint.py's native pytree format.
    """
    if "lm_head_s" in params:
        raise ValueError("export_llama_safetensors handles float trees only; "
                         "persist quantized trees with gofr_tpu.checkpoint")
    tensors: Dict[str, np.ndarray] = {}

    def host(x) -> np.ndarray:
        arr = np.asarray(x)
        return arr

    tensors["model.embed_tokens.weight"] = host(params["tok_emb"])
    tensors["model.norm.weight"] = host(params["final_norm"])
    tensors["lm_head.weight"] = np.ascontiguousarray(host(params["lm_head"]).T)
    layers = params["layers"]
    n_layers = layers["wq"].shape[0]
    for leaf, (suffix, transpose) in _LAYER_MAP.items():
        stacked = host(layers[leaf])
        for l in range(n_layers):
            t = stacked[l]
            tensors[f"model.layers.{l}.{suffix}"] = (
                np.ascontiguousarray(t.T) if transpose else t)
    write_safetensors(path, tensors, metadata)
