"""Data-pipeline stream statistics via LSketch (dense-LM telemetry seat).

Sketches the token-bigram stream of the training corpus: heavy-hitter
bigrams, per-band volumes, and windowed drift ("did the bigram mix change
over the last j subwindows?") — data-quality monitoring primitives at
sub-linear memory, straight from the paper's query set.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import EdgeBatch, LSketch, LSketchConfig, insert_batch
from repro.data.tokens import DEFAULT_BAND_VOCAB, bigram_stream, token_band


class BigramSketch:
    def __init__(self, window_steps: int = 64, subwindows: int = 8,
                 d: int = 256, n_bands: int = 4,
                 vocab_size: int = DEFAULT_BAND_VOCAB):
        self.n_bands = n_bands
        self.vocab_size = vocab_size
        self.cfg = LSketchConfig(
            d=d, n_blocks=n_bands, F=1024, r=4, s=8, c=8, k=subwindows,
            window_size=window_steps, pool_capacity=8192, seed=77)
        self.sketch = LSketch(self.cfg)
        self._step = 0

    def ingest_tokens(self, tokens: np.ndarray, step: int | None = None):
        st = bigram_stream(tokens, n_bands=self.n_bands,
                           vocab_size=self.vocab_size)
        t = self._step if step is None else step
        batch = EdgeBatch(
            src=jnp.asarray(st["src"]), dst=jnp.asarray(st["dst"]),
            src_label=jnp.asarray(st["src_label"]),
            dst_label=jnp.asarray(st["dst_label"]),
            edge_label=jnp.asarray(st["edge_label"]),
            weight=jnp.asarray(st["weight"]),
            time=jnp.asarray(np.full(len(st["src"]), t, np.int32)),
        )
        self.sketch.state = insert_batch(self.cfg, self.sketch.state, batch)
        self._step = t + 1
        return self

    def bigram_weight(self, a: int, b: int, last=None) -> int:
        # the query-side band MUST be the ingest-side band: one shared
        # pure function on the fixed vocab reference (regression-tested)
        band = lambda t: int(token_band(t, self.n_bands, self.vocab_size))
        return self.sketch.edge_weight(a, band(a), b, band(b), last=last)

    def band_volume(self, band: int, last=None) -> int:
        return self.sketch.label_aggregate(band, last=last)

    def drift(self, band: int, recent: int = 2) -> float:
        """Recent-vs-window volume ratio for a band (1.0 = stationary)."""
        whole = self.band_volume(band)
        if whole == 0:
            return 1.0
        rec = self.band_volume(band, last=recent)
        expected = whole * recent / self.cfg.k
        return float(rec / max(expected, 1e-9))
