"""Protocol-conforming fake providers (reference test pattern, SURVEY §4(a)):
deterministic embeddings so similarity thresholds are exactly testable, and
canned-JSON LLMs so consolidation runs without any model."""

import json
from typing import Dict, List, Optional


class MockEmbedder:
    """Deterministic: known texts map to fixed orthogonal-ish vectors; two
    texts are near-duplicates iff mapped to the same basis vector."""

    def __init__(self, dim: int = 8, table: Optional[Dict[str, int]] = None):
        self.dim = dim
        self.table = table or {}

    def _vec(self, text: str) -> List[float]:
        idx = self.table.get(text, abs(hash(text)) % self.dim)
        v = [0.0] * self.dim
        v[idx % self.dim] = 1.0
        return v

    def embed(self, text: str) -> List[float]:
        return self._vec(text)

    def batch_embed(self, texts: List[str]) -> List[List[float]]:
        return [self._vec(t) for t in texts]


class MockLLM:
    """Returns canned responses; optionally keyed by a substring sniffer
    (reference test_profile_update.py pattern, SURVEY §4)."""

    def __init__(self, response: str = "ok", sniffers: Optional[Dict[str, str]] = None):
        self.response = response
        self.sniffers = sniffers or {}
        self.calls: List[List[Dict]] = []

    def completion(self, messages, response_format=None) -> str:
        self.calls.append(messages)
        joined = " ".join(m["content"] for m in messages)
        for needle, resp in self.sniffers.items():
            if needle in joined:
                return resp
        return self.response

    def completion_stream(self, messages, response_format=None):
        yield self.completion(messages, response_format)


def extraction_response(facts) -> str:
    """Build a canned fact-extraction JSON payload."""
    return json.dumps({"memories": facts})


def file_ops(tel):
    """``store.file_ops{op}`` of a registry as {op: count}."""
    return {k.split("op=")[1].strip('"}'): v for k, v in tel.counters.items()
            if k.startswith("store.file_ops")}
