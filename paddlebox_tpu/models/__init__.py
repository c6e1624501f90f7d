"""Example CTR model family (SURVEY.md §7 stage 7)."""

from paddlebox_tpu.models.ctr_dnn import CtrDnn
from paddlebox_tpu.models.dcn import DCN
from paddlebox_tpu.models.decoder_lm import DecoderMoeLM
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.models.layers import bce_with_logits, init_mlp, linear, mlp
from paddlebox_tpu.models.longseq_ctr import LongSeqCtrDnn
from paddlebox_tpu.models.mmoe import MMoE
from paddlebox_tpu.models.pipelined_ctr import PipelinedCtrDnn
from paddlebox_tpu.models.rank_ctr import RankCtrDnn
from paddlebox_tpu.models.two_tower import TwoTower
from paddlebox_tpu.models.wide_deep import WideDeep
from paddlebox_tpu.models.xdeepfm import XDeepFM

__all__ = [
    "CtrDnn",
    "DCN",
    "DecoderMoeLM",
    "DeepFM",
    "LongSeqCtrDnn",
    "MMoE",
    "PipelinedCtrDnn",
    "RankCtrDnn",
    "TwoTower",
    "WideDeep",
    "XDeepFM",
    "bce_with_logits",
    "init_mlp",
    "linear",
    "mlp",
]
