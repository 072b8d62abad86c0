"""Inference runtime: tokenizer, engine, weight loading, batching.

This is the "local worker" tier that the reference's llm-gateway spec delegates to
external providers (modules/llm-gateway/docs/DESIGN.md:317-346 provider adapters) and
the BASELINE north star demands be native TPU: prefill/decode as XLA computations.
"""

from .engine import EngineConfig, SamplingParams
from .tokenizer import ByteTokenizer, Tokenizer, load_tokenizer

__all__ = [
    "ByteTokenizer",
    "EngineConfig",
    "SamplingParams",
    "Tokenizer",
    "load_tokenizer",
]
