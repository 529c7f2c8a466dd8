"""Fixture: a tool building the loop alone (parsed, never imported)."""

from gofr_tpu.tpu import engine
from gofr_tpu.tpu.engine import LLMEngine
from gofr_tpu.tpu.paging import PagedLLMEngine


def build(params, cfg):
    return LLMEngine(params, cfg)                # flagged


def build_by_module(params, cfg):
    return engine.LLMEngine(params, cfg)         # flagged


def build_right(params, cfg):
    return PagedLLMEngine(params, cfg)           # decoy


def annotated(e: LLMEngine) -> bool:
    return isinstance(e, LLMEngine)              # decoy: no call
