"""Fixture: the one place that may construct the loop (parsed only)."""

from .engine import LLMEngine


class PagedLLMEngine(LLMEngine):
    def _dispatch_decode(self):
        return 1

    def _finish_slot(self, slot):
        super()._finish_slot(slot)

    def _admission_ready(self, request):
        return False


def twin(params, cfg):
    return LLMEngine(params, cfg)        # paging.py: not flagged
