"""Model zoo public API."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax

from repro.configs.base import ArchConfig, ModelConfig
from repro.models import transformer as tfm
from repro.models.common import (
    abstract_params, init_params, param_axes, param_count,
)


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    @property
    def spec(self):
        return tfm.model_spec(self.cfg)

    def init(self, key: jax.Array):
        return init_params(key, self.spec, self.cfg.dtype)

    def abstract(self):
        return abstract_params(self.spec, self.cfg.dtype)

    def axes(self):
        return param_axes(self.spec)

    def param_count(self) -> int:
        return param_count(self.spec)

    def forward(self, params, batch, *, remat="none", scan_layers=True,
                last_only=False):
        return tfm.forward(params, batch, self.cfg, remat=remat,
                           scan_layers=scan_layers, last_only=last_only)

    def loss(self, params, batch, *, remat="none", scan_layers=True):
        return tfm.loss_fn(params, batch, self.cfg, remat=remat,
                           scan_layers=scan_layers)

    def init_cache(self, batch: int, max_seq: int, *, abstract=False):
        return tfm.init_cache(self.cfg, batch, max_seq, abstract=abstract)

    def cache_axes(self):
        return tfm.cache_axes(self.cfg)

    def decode_step(self, params, cache, token, pos):
        return tfm.decode_step(params, cache, token, pos, self.cfg)

    @property
    def chunked_prefill(self) -> bool:
        return tfm.chunked_prefill(self.cfg)

    def prefill_chunk(self, params, cache, tokens, start, last):
        return tfm.prefill_chunk(params, cache, tokens, start, last, self.cfg)

    def prime_cross_cache(self, params, cache, image_embeds):
        return tfm.prime_cross_cache(params, cache, image_embeds, self.cfg)


def build_model(arch: ArchConfig | ModelConfig) -> Model:
    cfg = arch.model if isinstance(arch, ArchConfig) else arch
    return Model(cfg)
