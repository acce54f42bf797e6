"""Framework-wide enums.

Mirrors the *vocabulary* of the reference's include/flexflow/ffconst.h (loss,
metrics, activation, aggregation, datatype, op-type enums) so that a FlexFlow
user finds the same names; values are our own.
"""

import enum

import jax.numpy as jnp


class DataType(enum.Enum):
    DT_BOOLEAN = "bool"
    DT_INT32 = "int32"
    DT_INT64 = "int64"
    DT_HALF = "float16"
    DT_BFLOAT16 = "bfloat16"
    DT_FLOAT = "float32"
    DT_DOUBLE = "float64"
    DT_INT4 = "int4"
    DT_INT8 = "int8"
    DT_NONE = "none"

    def to_jnp(self):
        if self == DataType.DT_NONE:
            raise ValueError("DT_NONE has no jnp dtype")
        if self == DataType.DT_INT4:
            return jnp.int4
        return jnp.dtype(self.value)

    @staticmethod
    def from_jnp(dtype) -> "DataType":
        return _JNP_TO_DT[jnp.dtype(dtype).name]


_JNP_TO_DT = {
    "bool": DataType.DT_BOOLEAN,
    "int32": DataType.DT_INT32,
    "int64": DataType.DT_INT64,
    "float16": DataType.DT_HALF,
    "bfloat16": DataType.DT_BFLOAT16,
    "float32": DataType.DT_FLOAT,
    "float64": DataType.DT_DOUBLE,
    "int4": DataType.DT_INT4,
    "int8": DataType.DT_INT8,
}


class ActiMode(enum.Enum):
    AC_MODE_NONE = 10
    AC_MODE_RELU = 11
    AC_MODE_SIGMOID = 12
    AC_MODE_TANH = 13
    AC_MODE_GELU = 14


class AggrMode(enum.Enum):
    AGGR_MODE_NONE = 20
    AGGR_MODE_SUM = 21
    AGGR_MODE_AVG = 22


class PoolType(enum.Enum):
    POOL_MAX = 30
    POOL_AVG = 31


class LossType(enum.Enum):
    LOSS_CATEGORICAL_CROSSENTROPY = 50
    LOSS_SPARSE_CATEGORICAL_CROSSENTROPY = 51
    LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE = 52
    LOSS_MEAN_SQUARED_ERROR_SUM_REDUCE = 53
    LOSS_IDENTITY = 54


class MetricsType(enum.Enum):
    METRICS_ACCURACY = 1001
    METRICS_CATEGORICAL_CROSSENTROPY = 1002
    METRICS_SPARSE_CATEGORICAL_CROSSENTROPY = 1004
    METRICS_MEAN_SQUARED_ERROR = 1008
    METRICS_ROOT_MEAN_SQUARED_ERROR = 1016
    METRICS_MEAN_ABSOLUTE_ERROR = 1032


class CompMode(enum.Enum):
    COMP_MODE_TRAINING = 70
    COMP_MODE_INFERENCE = 71


class ParameterSyncType(enum.Enum):
    NONE = 80
    PS = 81          # parameter-server style (grads gathered to replica then broadcast)
    NCCL = 82        # reference name; here it means XLA psum over the mesh


class InferenceMode(enum.Enum):
    INC_DECODING_MODE = 2001
    BEAM_SEARCH_MODE = 2002
    TREE_VERIFY_MODE = 2003


class RequestType(enum.Enum):
    REQ_INFERENCE = 4001
    REQ_FINETUNING = 4002


class OpType(enum.Enum):
    """Operator types — the union of the reference's OperatorType enum members
    that this framework implements (reference include/flexflow/ffconst.h:41+)."""

    NOOP = enum.auto()
    INPUT = enum.auto()
    WEIGHT = enum.auto()
    # dense / classic
    LINEAR = enum.auto()
    CONV2D = enum.auto()
    POOL2D = enum.auto()
    BATCHNORM = enum.auto()
    LAYERNORM = enum.auto()
    RESIDUAL_LAYERNORM = enum.auto()
    ADD_BIAS_RESIDUAL_LAYERNORM = enum.auto()
    RMS_NORM = enum.auto()
    RESIDUAL_RMS_NORM = enum.auto()
    EMBEDDING = enum.auto()
    DROPOUT = enum.auto()
    MULTIHEAD_ATTENTION = enum.auto()
    INC_MULTIHEAD_SELF_ATTENTION = enum.auto()
    SPEC_INC_MULTIHEAD_SELF_ATTENTION = enum.auto()
    TREE_INC_MULTIHEAD_SELF_ATTENTION = enum.auto()
    SIGMOID_SILU_MULTI = enum.auto()
    # elementwise
    EW_ADD = enum.auto()
    EW_SUB = enum.auto()
    EW_MUL = enum.auto()
    EW_DIV = enum.auto()
    EW_MAX = enum.auto()
    EW_MIN = enum.auto()
    RELU = enum.auto()
    IDENTITY = enum.auto()
    SIGMOID = enum.auto()
    TANH = enum.auto()
    ELU = enum.auto()
    GELU = enum.auto()
    EXP = enum.auto()
    SIN = enum.auto()
    COS = enum.auto()
    RSQRT = enum.auto()
    POW = enum.auto()
    SCALAR_MULTIPLY = enum.auto()
    SCALAR_ADD = enum.auto()
    SCALAR_SUB = enum.auto()
    SCALAR_TRUE_DIV = enum.auto()
    # shape
    CONCAT = enum.auto()
    SPLIT = enum.auto()
    RESHAPE = enum.auto()
    SLICE = enum.auto()
    TRANSPOSE = enum.auto()
    REVERSE = enum.auto()
    FLAT = enum.auto()
    CAST = enum.auto()
    # constants / selection (torch-frontend lowering targets)
    CONSTANT = enum.auto()
    WHERE = enum.auto()
    COMPARE = enum.auto()
    BROADCAST_TO = enum.auto()
    # reductions / algebra
    SOFTMAX = enum.auto()
    BATCH_MATMUL = enum.auto()
    REDUCE_SUM = enum.auto()
    REDUCE_MEAN = enum.auto()
    MEAN = enum.auto()
    GATHER = enum.auto()
    TOPK = enum.auto()
    ARG_TOPK = enum.auto()
    ARGMAX = enum.auto()
    SAMPLING = enum.auto()
    BEAM_TOPK = enum.auto()
    # MoE
    GROUP_BY = enum.auto()
    AGGREGATE = enum.auto()
    AGG_SPEC = enum.auto()
    EXPERTS = enum.auto()
    MOE_EXPERTS = enum.auto()
    CACHE = enum.auto()
    # parallel ops (PCG nodes in the reference; sharding boundaries here)
    REPARTITION = enum.auto()
    COMBINE = enum.auto()
    REPLICATE = enum.auto()
    REDUCTION = enum.auto()
    ALLREDUCE = enum.auto()
    FUSED_PARALLEL = enum.auto()
    # fused
    FUSED = enum.auto()
    # serving attention over a latent cache (ops/latent_attention.py); last,
    # so that no earlier member's value moved
    INC_MULTIHEAD_LATENT_ATTENTION = enum.auto()
    # serving attention in a convolved latent, with a tail a slot
    # (ops/cca_attention.py)
    INC_MULTIHEAD_CCA_ATTENTION = enum.auto()
    # serving attention by a gated delta rule, a recurrent state a slot
    # (ops/kda_attention.py)
    INC_KDA_ATTENTION = enum.auto()
    # a serving state-space mixer (Mamba-2), a recurrent state a slot
    # (ops/ssd_mixer.py)
    INC_SSD_MIXER = enum.auto()
    # a loop region's two ends and the gate that reads its passes
    # (ops/loop.py; FFModel.loop_begin / loop_end / loop_exit)
    LOOP_BEGIN = enum.auto()
    LOOP_END = enum.auto()
    LOOP_EXIT = enum.auto()
