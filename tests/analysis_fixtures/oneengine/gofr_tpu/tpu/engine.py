"""Fixture: the loop file reaching for a forward (parsed, never imported)."""

from ..models.llama import LlamaConfig, params_nbytes    # decoys: pass
from ..models.llama import llama_decode_step             # flagged


class LLMEngine:
    def score(self):
        from ..models.llama import llama_forward_nocache     # excepted by name
        from ..models.llama import init_kv_cache              # flagged

        return llama_forward_nocache, init_kv_cache

    def plan(self):
        from ..models.llama import llama_prefill  # lint: oneengine-ok fixture pragma

        return llama_prefill, LlamaConfig, params_nbytes, llama_decode_step

    def _loop(self):
        self._dispatch_decode()
        self._finish_slot(None)
        self._admission_ready(None)
        self._note_nothing()

    def _dispatch_decode(self):
        """A hook: filled in paging.py."""
        raise NotImplementedError

    def _export_slot_kv(self):
        """A hook nobody fills: flagged."""
        raise NotImplementedError

    def _finish_slot(self, slot):
        return slot                 # decoy: paging.py extends it via super()

    def _admission_ready(self, request):
        return True                 # flagged: replaced without super()

    def _note_nothing(self):
        return 0                    # decoy: not overridden at all
