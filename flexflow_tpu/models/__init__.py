"""Serving model zoo.

Capability parity with the reference model zoo (reference inference/models/
llama.cc, opt.cc, falcon.cc, mpt.cc, starcoder.cc and their Python twins in
python/flexflow/serve/models/; OLMoE, EXAONE-MoE, Mistral-4, SDAR-MoE, LongCat-Flash, ZAYA1 and Solar-Open2, sparse-expert families, EvaByte, a byte-level model of chunked attention, and Granite-4.0-H, a hybrid of state-space mixers and attention, and Ouro, a stack of blocks run several times over one set of weights, are beyond it): each model family is a builder that records
the decoder graph through the FFModel op-builder surface, plus a HuggingFace
state-dict name mapping so real checkpoints load. ``FAMILIES`` maps the HF
``model_type`` to the family (the reference's ModelType enum +
serve.py architecture dispatch).
"""

import dataclasses
from typing import Callable, Optional

from flexflow_tpu.models import evabyte as _evabyte
from flexflow_tpu.models import exaone_moe as _exaone_moe
from flexflow_tpu.models import falcon as _falcon
from flexflow_tpu.models import granite_hybrid as _granite_hybrid
from flexflow_tpu.models import llama as _llama
from flexflow_tpu.models import longcat_flash as _longcat_flash
from flexflow_tpu.models import mistral4 as _mistral4
from flexflow_tpu.models import mpt as _mpt
from flexflow_tpu.models import olmoe as _olmoe
from flexflow_tpu.models import opt as _opt
from flexflow_tpu.models import ouro as _ouro
from flexflow_tpu.models import sdar_moe as _sdar_moe
from flexflow_tpu.models import solar_open2 as _solar_open2
from flexflow_tpu.models import starcoder as _starcoder
from flexflow_tpu.models import zaya as _zaya
from flexflow_tpu.models.evabyte import EvaByteConfig, create_evabyte_model
from flexflow_tpu.models.exaone_moe import (ExaoneMoEConfig,
                                            create_exaone_moe_model)
from flexflow_tpu.models.falcon import FalconConfig, create_falcon_model
from flexflow_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                create_granite_hybrid_model)
from flexflow_tpu.models.hf_utils import load_hf_state_dict
from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model
from flexflow_tpu.models.longcat_flash import (LongcatFlashConfig,
                                               create_longcat_flash_model)
from flexflow_tpu.models.mistral4 import (Mistral4Config,
                                          create_mistral4_model)
from flexflow_tpu.models.mpt import MPTConfig, create_mpt_model
from flexflow_tpu.models.olmoe import OLMoEConfig, create_olmoe_model
from flexflow_tpu.models.opt import OPTConfig, create_opt_model
from flexflow_tpu.models.ouro import OuroConfig, create_ouro_model
from flexflow_tpu.models.sdar_moe import SDARMoEConfig, create_sdar_moe_model
from flexflow_tpu.models.solar_open2 import (SolarOpen2Config,
                                             create_solar_open2_model)
from flexflow_tpu.models.starcoder import (STARCODERConfig,
                                           create_starcoder_model)
from flexflow_tpu.models.zaya import ZayaConfig, create_zaya_model


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    """One serving model family (reference ModelType enum member)."""

    name: str
    config_cls: type
    build: Callable          # (ffmodel, config, mode=..., ...) -> out tensor
    hf_weight_map: Callable  # (config) -> {hf_key: (layer, weight, transpose)}
    preprocess: Optional[Callable] = None  # (state_dict, config) -> None

    def load_hf(self, ffmodel, config, state_dict, strict: bool = True) -> int:
        pre = ((lambda sd: self.preprocess(sd, config))
               if self.preprocess else None)
        return load_hf_state_dict(ffmodel, state_dict,
                                  self.hf_weight_map(config),
                                  strict=strict, preprocess=pre)


FAMILIES = {
    "llama": ModelFamily("llama", LLAMAConfig, create_llama_model,
                         _llama.hf_weight_map,
                         getattr(_llama, "preprocess_hf_state_dict", None)),
    "opt": ModelFamily("opt", OPTConfig, create_opt_model,
                       _opt.hf_weight_map, _opt.preprocess_hf_state_dict),
    "falcon": ModelFamily("falcon", FalconConfig, create_falcon_model,
                          _falcon.hf_weight_map,
                          _falcon.preprocess_hf_state_dict),
    "mpt": ModelFamily("mpt", MPTConfig, create_mpt_model,
                       _mpt.hf_weight_map, _mpt.preprocess_hf_state_dict),
    "olmoe": ModelFamily("olmoe", OLMoEConfig, create_olmoe_model,
                         _olmoe.hf_weight_map,
                         _olmoe.preprocess_hf_state_dict),
    "exaone_moe": ModelFamily("exaone_moe", ExaoneMoEConfig,
                              create_exaone_moe_model,
                              _exaone_moe.hf_weight_map,
                              _exaone_moe.preprocess_hf_state_dict),
    "mistral4": ModelFamily("mistral4", Mistral4Config,
                            create_mistral4_model, _mistral4.hf_weight_map,
                            _mistral4.preprocess_hf_state_dict),
    "longcat_flash": ModelFamily("longcat_flash", LongcatFlashConfig,
                                 create_longcat_flash_model,
                                 _longcat_flash.hf_weight_map,
                                 _longcat_flash.preprocess_hf_state_dict),
    "sdar_moe": ModelFamily("sdar_moe", SDARMoEConfig, create_sdar_moe_model,
                            _sdar_moe.hf_weight_map,
                            _sdar_moe.preprocess_hf_state_dict),
    "evabyte": ModelFamily("evabyte", EvaByteConfig, create_evabyte_model,
                           _evabyte.hf_weight_map,
                           _evabyte.preprocess_hf_state_dict),
    "gpt_bigcode": ModelFamily("gpt_bigcode", STARCODERConfig,
                               create_starcoder_model,
                               _starcoder.hf_weight_map,
                               _starcoder.preprocess_hf_state_dict),
    # its attention op (ops/cca_attention.py) is imported by the builder
    # call that records such a layer, not here
    # (so is this one's ops/kda_attention.py)
    "solar_open2": ModelFamily("solar_open2", SolarOpen2Config,
                               create_solar_open2_model,
                               _solar_open2.hf_weight_map,
                               _solar_open2.preprocess_hf_state_dict),
    # (and this one's ops/ssd_mixer.py)
    "granitemoehybrid": ModelFamily("granitemoehybrid", GraniteHybridConfig,
                                    create_granite_hybrid_model,
                                    _granite_hybrid.hf_weight_map,
                                    _granite_hybrid.preprocess_hf_state_dict),
    # one stack of blocks run several times: a loop region (ops/loop.py)
    "ouro": ModelFamily("ouro", OuroConfig, create_ouro_model,
                        _ouro.hf_weight_map, _ouro.preprocess_hf_state_dict),
    "zaya": ModelFamily("zaya", ZayaConfig, create_zaya_model,
                        _zaya.hf_weight_map,
                        _zaya.preprocess_hf_state_dict),
}
FAMILIES["starcoder"] = FAMILIES["gpt_bigcode"]
# Legacy HF names for early Falcon checkpoints (tiiuae/falcon-7b pre-rename).
FAMILIES["RefinedWeb"] = FAMILIES["RefinedWebModel"] = FAMILIES["falcon"]


def family_for_hf_config(hf_config) -> ModelFamily:
    """Resolve a transformers config (or dict) to its model family."""
    mt = (hf_config.get("model_type") if isinstance(hf_config, dict)
          else getattr(hf_config, "model_type", None))
    if mt not in FAMILIES:
        raise ValueError(
            f"unsupported model_type {mt!r}; supported: "
            f"{sorted(set(f.name for f in FAMILIES.values()))}")
    return FAMILIES[mt]


__all__ = [
    "EvaByteConfig",
    "ExaoneMoEConfig",
    "FAMILIES",
    "FalconConfig",
    "GraniteHybridConfig",
    "LLAMAConfig",
    "LongcatFlashConfig",
    "MPTConfig",
    "Mistral4Config",
    "ModelFamily",
    "OLMoEConfig",
    "OPTConfig",
    "OuroConfig",
    "SDARMoEConfig",
    "STARCODERConfig",
    "SolarOpen2Config",
    "ZayaConfig",
    "create_evabyte_model",
    "create_exaone_moe_model",
    "create_falcon_model",
    "create_granite_hybrid_model",
    "create_llama_model",
    "create_longcat_flash_model",
    "create_mistral4_model",
    "create_mpt_model",
    "create_olmoe_model",
    "create_opt_model",
    "create_ouro_model",
    "create_sdar_moe_model",
    "create_solar_open2_model",
    "create_starcoder_model",
    "create_zaya_model",
    "family_for_hf_config",
    "load_hf_state_dict",
]
