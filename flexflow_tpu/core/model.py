"""FFModel: the model container and op-builder API.

Capability parity with the reference ``FFModel`` (reference
include/flexflow/model.h:393, src/runtime/model.cc): users record layers via
builder methods (dense, conv2d, embedding, attention, ...), then ``compile``
lowers the layer graph into an executable — here a pure jax function jitted
over a device mesh instead of Legion index-task launches routed by a custom
mapper. The training verbs (forward/backward/update, fit/eval) mirror
model.cc:2784/2807/2838 and the Python ``fit`` (flexflow_cffi.py:3534).

TPU-first design notes:
* One jitted ``train_step`` fuses forward+backward+update (the reference
  launches hundreds of Legion tasks per iteration; XLA compiles the whole
  step into one program — its fusion subsumes the reference's FusedOp).
* Parallelism is GSPMD: params/batches carry NamedShardings from the mesh
  (flexflow_tpu/parallel); gradient sync is inserted by XLA (the reference
  needs explicit NCCL allreduce tasks or parameter-server reductions).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from flexflow_tpu.config import FFConfig
from flexflow_tpu.core.layer import Layer, WeightSpec
from flexflow_tpu.core.tensor import Tensor
from flexflow_tpu.ffconst import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OpType,
    PoolType,
)
from flexflow_tpu.ops.base import OpContext, get_op_impl, stable_hash
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.parallel.spec import ShardingPolicy
from flexflow_tpu.training.dataloader import minibatches
from flexflow_tpu.training.loss import compute_loss
from flexflow_tpu.training.metrics import PerfMetrics, compute_step_metrics


def _normalize_regularizer(reg):
    """Normalize a regularizer spec to None or a non-empty list of
    ("l1"|"l2", float) pairs; reject unknown kinds with a clear error."""
    if reg is None:
        return None
    if hasattr(reg, "to_attr"):          # keras.regularizers.* instance
        reg = reg.to_attr()
    if isinstance(reg, (list, tuple)) and reg \
            and not isinstance(reg[0], (list, tuple)):
        reg = [reg]                      # single ("l2", c) pair
    out = []
    for item in reg or []:
        kind, coeff = item
        if kind not in ("l1", "l2"):
            raise ValueError(f"unknown regularizer kind {kind!r} "
                             f"(expected 'l1' or 'l2')")
        if coeff:
            out.append((kind, float(coeff)))
    return out or None


class LoopRegion:
    """A span of the layer list that runs ``steps`` times over one set of
    weights inside one device loop (``FFModel.loop_begin`` / ``loop_end``,
    ops/loop.py): its two end layers, the ``span`` between them, and, once
    the model is compiled, ``cache_layers``: the span's attention layers,
    each of which keeps a cache plane a pass."""

    # what a span's stateful layer may be: a plain k/v cache, a plane a pass
    _PLAIN = (OpType.INC_MULTIHEAD_SELF_ATTENTION,)
    _REFUSED = {
        OpType.INC_KDA_ATTENTION: "a layer that keeps a recurrent state",
        OpType.INC_SSD_MIXER: "a state-space mixer, which keeps a "
                              "recurrent state",
        OpType.INC_MULTIHEAD_LATENT_ATTENTION: "a latent attention layer",
        OpType.INC_MULTIHEAD_CCA_ATTENTION: "an attention layer that "
                                            "carries a tail",
        OpType.TREE_INC_MULTIHEAD_SELF_ATTENTION: "tree verification",
        OpType.SPEC_INC_MULTIHEAD_SELF_ATTENTION: "beam drafting",
        OpType.MOE_EXPERTS: "a routed-expert layer (its counters are a row "
                            "a layer, not a row a layer and pass)",
    }

    def __init__(self, begin: Layer):
        self.begin, self.end = begin, None
        self.span: List[Layer] = []
        self.cache_layers: List[Layer] = []
        self.inside = frozenset()   # the two ends and the span (``check``)

    @property
    def steps(self) -> int:
        return self.begin.attrs["steps"]

    def check(self, model):
        """Refuse what a region cannot hold: any state but a plain k/v
        cache a pass, and a value that leaves the span by another way than
        ``loop_end``."""
        def refuse(what):
            raise NotImplementedError(
                f"{what} inside a loop region is not supported: a pass "
                "keeps its own plane of a plain k/v stack and nothing else "
                "(ops/loop.py; a state a layer would be shared by the "
                "passes, a different result)")

        self.inside = frozenset((self.begin, *self.span, self.end))
        for ly in self.span:
            if ly.op_type in self._REFUSED:
                refuse(self._REFUSED[ly.op_type])
            if ly.attrs.get("sliding_window") is not None:
                refuse("a windowed attention layer")
            if ly.attrs.get("eva_window") is not None:
                refuse("a chunked attention layer")
            if "block_length" in ly.attrs:
                refuse("a block-diffusion layer")
            if (hasattr(get_op_impl(ly.op_type), "init_state")
                    and ly.op_type not in self._PLAIN):
                refuse(f"a {ly.op_type.name} layer, which keeps state")
        made = {t.tensor_id for ly in self.span for t in ly.outputs}
        for ly in model.layers:
            if ly not in self.inside and any(
                    t.tensor_id in made for t in ly.inputs):
                raise ValueError(
                    f"{ly.name} reads a value of a loop region's span: hand "
                    "it out through loop_end(collect=[...])")
        self.cache_layers = [ly for ly in self.span
                             if ly.op_type in self._PLAIN]


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self.layers: List[Layer] = []
        self.input_tensors: List[Tensor] = []
        self.label_tensor: Optional[Tensor] = None
        self._compiled = False
        self.params: Dict[str, Dict[str, jnp.ndarray]] = {}
        self.op_state: Dict[str, Any] = {}
        self.opt_state = None
        self.optimizer = None
        self.loss_type: Optional[LossType] = None
        self.metrics: List[MetricsType] = []
        self.mesh = None
        self.policy: Optional[ShardingPolicy] = None
        self.strategy = None    # search/strategy.py Strategy when auto_parallel
        self._branch_plan = None
        self._train_step = None
        self._eval_step = None
        self._perf = PerfMetrics()
        from flexflow_tpu.utils.profiling import StepTimer
        self._step_timer = StepTimer(enabled=True)
        self._rng = jax.random.PRNGKey(self.config.seed)
        self._cached_activations = None
        self._cached_grads = None
        self._pending_batch = None
        self._layer_name_counts: Dict[str, int] = {}
        # Serving position input (models with learned positional embeddings:
        # OPT, StarCoder). Reference FFModel::set_position_offset + the
        # position_input tensor created by those model builders.
        self.position_input_tensor: Optional[Tensor] = None
        self.position_offset: int = 0
        # pipeline-parallel serving plan (set by compile when
        # pipeline_parallelism_degree > 1; see serve/pipeline_plan.py)
        self._pp_plan = None
        self._pp_segment_fn = None
        # loop regions, in order (loop_begin / loop_end; ops/loop.py)
        self._loop_regions: List[LoopRegion] = []
        self.loop_region: Optional[LoopRegion] = None

    # ==================================================================
    # Tensor / layer creation
    # ==================================================================
    def create_tensor(self, dims: Sequence[int], dtype: DataType = DataType.DT_FLOAT,
                      create_grad: bool = True, name: str = "") -> Tensor:
        t = Tensor(tuple(dims), dtype, name=name or f"input_{len(self.input_tensors)}",
                   model=self)
        self.input_tensors.append(t)
        return t

    def create_position_tensor(self, dims: Sequence[int]) -> Tensor:
        """Input tensor fed with absolute token positions (+ offset) by the
        InferenceManager each step (reference RM_LOAD_POSITION task)."""
        t = self.create_tensor(dims, DataType.DT_INT32, name="position_input")
        self.position_input_tensor = t
        return t

    def set_position_offset(self, offset: int):
        """Reference FFModel::set_position_offset (OPT feeds positions+2)."""
        self.position_offset = offset

    def _add_layer(self, op_type: OpType, inputs: List[Tensor],
                   attrs: Dict[str, Any], name: Optional[str] = None
                   ) -> Union[Tensor, List[Tensor]]:
        attrs = dict(attrs)
        attrs.setdefault("op_type", op_type)
        layer = Layer(op_type, name, inputs, attrs,
                      counts=self._layer_name_counts)
        impl = get_op_impl(op_type)
        input_specs = [(t.dims, t.dtype) for t in inputs]
        out_specs = impl.infer_output_specs(attrs, input_specs)
        layer.weights = impl.weight_specs(attrs, input_specs)
        outputs = []
        for i, (shape, dtype) in enumerate(out_specs):
            outputs.append(Tensor(shape, dtype, name=f"{layer.name}.out{i}",
                                  owner_layer=layer, owner_idx=i, model=self))
        layer.outputs = outputs
        self.layers.append(layer)
        if len(outputs) == 1:
            return outputs[0]
        return outputs

    # ==================================================================
    # Op-builder surface (reference model.h:500-900 builder methods)
    # ==================================================================
    def dense(self, input: Tensor, out_dim: int,
              activation: ActiMode = ActiMode.AC_MODE_NONE,
              use_bias: bool = True, datatype: Optional[DataType] = None,
              kernel_initializer=None, bias_initializer=None,
              kernel_regularizer=None, keep_f32_logits: bool = False,
              data_type: Optional[DataType] = None,
              name: Optional[str] = None,
              tied_to: Optional[str] = None) -> Tensor:
        """``tied_to``: the name of an ``embedding`` layer whose table this
        layer multiplies by, transposed, in place of a kernel of its own (a
        tied head: one array, quantised once, read by both; no bias).
        kernel_regularizer: ("l1"|"l2", coeff) or a list of such pairs —
        added to the training loss (reference keras regularizers).
        keep_f32_logits: for LM heads feeding argmax/sampling — emit the
        gemm's f32 accumulator instead of rounding to the compute dtype
        (bf16 ties flip greedy argmax between serving programs).
        ``data_type`` and ``datatype`` are synonyms: the reference's cffi
        dense() spells it ``datatype`` while every other builder here uses
        ``data_type`` — both call styles must work (r1 VERDICT)."""
        if (datatype is not None and data_type is not None
                and datatype != data_type):
            raise ValueError(
                f"dense(): conflicting datatype={datatype} and "
                f"data_type={data_type} (they are synonyms)")
        return self._add_layer(OpType.LINEAR, [input], dict(
            out_dim=out_dim, activation=activation, use_bias=use_bias,
            data_type=datatype if datatype is not None else data_type,
            kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer,
            keep_f32_logits=keep_f32_logits,
            kernel_regularizer=_normalize_regularizer(kernel_regularizer),
            # only a tied layer carries the key
            **({} if tied_to is None else {"tied_to": tied_to})),
            name)

    def conv2d(self, input: Tensor, out_channels: int, kernel_h: int,
               kernel_w: int, stride_h: int, stride_w: int, padding_h: int,
               padding_w: int, activation: ActiMode = ActiMode.AC_MODE_NONE,
               groups: int = 1, use_bias: bool = True,
               kernel_initializer=None, bias_initializer=None,
               kernel_regularizer=None,
               name: Optional[str] = None) -> Tensor:
        return self._add_layer(OpType.CONV2D, [input], dict(
            out_channels=out_channels, kernel_h=kernel_h, kernel_w=kernel_w,
            stride_h=stride_h, stride_w=stride_w, padding_h=padding_h,
            padding_w=padding_w, activation=activation, groups=groups,
            use_bias=use_bias, kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer,
            kernel_regularizer=_normalize_regularizer(kernel_regularizer)),
            name)

    def pool2d(self, input: Tensor, kernel_h: int, kernel_w: int,
               stride_h: int, stride_w: int, padding_h: int, padding_w: int,
               pool_type: PoolType = PoolType.POOL_MAX,
               activation: ActiMode = ActiMode.AC_MODE_NONE,
               name: Optional[str] = None) -> Tensor:
        return self._add_layer(OpType.POOL2D, [input], dict(
            kernel_h=kernel_h, kernel_w=kernel_w, stride_h=stride_h,
            stride_w=stride_w, padding_h=padding_h, padding_w=padding_w,
            pool_type=pool_type, activation=activation), name)

    def batch_norm(self, input: Tensor, relu: bool = True,
                   name: Optional[str] = None) -> Tensor:
        return self._add_layer(OpType.BATCHNORM, [input],
                               dict(relu=relu), name)

    def layer_norm(self, input: Tensor, axes: Sequence[int],
                   elementwise_affine: bool = True, eps: float = 1e-5,
                   use_bias: bool = True, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OpType.LAYERNORM, [input], dict(
            axes=tuple(axes), elementwise_affine=elementwise_affine, eps=eps,
            use_bias=use_bias), name)

    def residual_layer_norm(self, input: Tensor, residual1: Tensor,
                            residual2: Optional[Tensor] = None,
                            use_two_residuals: bool = False,
                            axes: Sequence[int] = (-1,),
                            elementwise_affine: bool = True, eps: float = 1e-5,
                            use_bias: bool = True,
                            name: Optional[str] = None) -> List[Tensor]:
        inputs = [input, residual1] + ([residual2] if use_two_residuals else [])
        return self._add_layer(OpType.RESIDUAL_LAYERNORM, inputs, dict(
            axes=tuple(a % input.num_dims for a in axes),
            elementwise_affine=elementwise_affine, eps=eps,
            use_bias=use_bias), name)

    def add_bias_residual_layer_norm(self, input: Tensor, residual: Tensor,
                                     axes: Sequence[int] = (-1,),
                                     elementwise_affine: bool = True,
                                     eps: float = 1e-5, use_bias: bool = True,
                                     name: Optional[str] = None) -> List[Tensor]:
        return self._add_layer(OpType.ADD_BIAS_RESIDUAL_LAYERNORM,
                               [input, residual], dict(
            axes=tuple(a % input.num_dims for a in axes),
            elementwise_affine=elementwise_affine, eps=eps,
            use_bias=use_bias), name)

    def rms_norm(self, input: Tensor, eps: float = 1e-6,
                 dim: Optional[int] = None, name: Optional[str] = None,
                 unit_offset: bool = False,
                 data_type: Optional[DataType] = None,
                 initializer=None) -> Tensor:
        """``unit_offset``: the weight is an offset from one, ``x / rms *
        (1 + g)``; ``data_type``: the output's, where it is not the
        input's; ``initializer``: the weight's, where it is not ones
        (ops/norm.RMSNorm). Only a model that asks carries the keys."""
        return self._add_layer(OpType.RMS_NORM, [input], dict(
            eps=eps, dim=dim or input.dims[-1],
            **({"unit_offset": True} if unit_offset else {}),
            **({} if data_type is None else {"data_type": data_type}),
            **({} if initializer is None else {"initializer": initializer})),
            name)

    def residual_rms_norm(self, input1: Tensor, input2: Tensor,
                          eps: float = 1e-6, dim: Optional[int] = None,
                          name: Optional[str] = None) -> List[Tensor]:
        return self._add_layer(OpType.RESIDUAL_RMS_NORM, [input1, input2], dict(
            eps=eps, dim=dim or input1.dims[-1]), name)

    def sigmoid_silu_multi(self, input1: Tensor, input2: Tensor,
                           name: Optional[str] = None) -> Tensor:
        return self._add_layer(OpType.SIGMOID_SILU_MULTI, [input1, input2],
                               {}, name)

    def embedding(self, input: Tensor, num_entries: int, out_dim: int,
                  aggr: AggrMode = AggrMode.AGGR_MODE_NONE,
                  dtype: DataType = DataType.DT_FLOAT,
                  kernel_initializer=None, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OpType.EMBEDDING, [input], dict(
            num_entries=num_entries, out_dim=out_dim, aggr=aggr,
            data_type=dtype, kernel_initializer=kernel_initializer), name)

    def dropout(self, input: Tensor, rate: float = 0.5, seed: int = 0,
                name: Optional[str] = None) -> Tensor:
        return self._add_layer(OpType.DROPOUT, [input],
                               dict(rate=rate, seed=seed), name)

    def multihead_attention(self, query: Tensor, key: Tensor, value: Tensor,
                            embed_dim: int, num_heads: int,
                            kdim: int = 0, vdim: int = 0, dropout: float = 0.0,
                            bias: bool = True, add_bias_kv: bool = False,
                            add_zero_attn: bool = False,
                            kernel_initializer=None, causal: bool = False,
                            name: Optional[str] = None) -> Tensor:
        return self._add_layer(OpType.MULTIHEAD_ATTENTION, [query, key, value],
                               dict(embed_dim=embed_dim, num_heads=num_heads,
                                    kdim=kdim or embed_dim, vdim=vdim or embed_dim,
                                    dropout=dropout, causal=causal, bias=bias,
                                    add_bias_kv=add_bias_kv,
                                    add_zero_attn=add_zero_attn,
                                    kernel_initializer=kernel_initializer), name)

    # --- serving attention family (reference model.h:700-790:
    # inc_multihead_self_attention / inc_multiquery_self_attention and the
    # spec_inc_* / tree_inc_* variants) ---
    def _serving_attention(self, op_type: OpType, input: Tensor,
                           embed_dim: int, num_q_heads: int, num_kv_heads: int,
                           kdim: int, vdim: int, dropout: float, bias: bool,
                           add_bias_kv: bool, add_zero_attn: bool,
                           data_type, kernel_initializer,
                           apply_rotary_embedding: bool, scaling_query: bool,
                           scaling_factor: float, qk_prod_scaling: bool,
                           position_bias: bool, rope_theta: float,
                           name, qk_norm_eps: Optional[float] = None,
                           qk_norm_per_head: bool = False,
                           sliding_window: Optional[int] = None,
                           block_length: Optional[int] = None,
                           eva_window: Optional[int] = None,
                           chunk_size: Optional[int] = None,
                           output_gate: bool = False) -> Tensor:
        if eva_window is not None:
            self._check_chunked(op_type, eva_window, chunk_size,
                                sliding_window, block_length, position_bias)
        if block_length is not None and (
                op_type != OpType.INC_MULTIHEAD_SELF_ATTENTION
                or sliding_window is not None or position_bias):
            raise NotImplementedError(
                "an attention layer of a block-diffusion model is served by "
                f"incremental decoding only, not as {op_type.name}, and "
                "with neither a window nor a position bias: a query sees "
                "its whole block, which tree verification, beam drafting, "
                "a ring and ALiBi all read as a causal position")
        if sliding_window is not None \
                and op_type != OpType.INC_MULTIHEAD_SELF_ATTENTION:
            raise NotImplementedError(
                "a windowed attention layer is served by incremental "
                f"decoding only, not as {op_type.name}: tree verification "
                "and beam drafting stage and move cache positions that a "
                "ring (ops/kv_layout.py) does not keep")
        if add_bias_kv or add_zero_attn:
            raise NotImplementedError(
                "add_bias_kv/add_zero_attn are not supported by the serving "
                "attention ops (the reference also ignores them here)")
        if vdim and vdim != (kdim or embed_dim):
            raise NotImplementedError("vdim != kdim serving attention")
        head_dim = (kdim or embed_dim) // num_q_heads
        return self._add_layer(op_type, [input], dict(
            embed_dim=embed_dim, num_q_heads=num_q_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim, dropout=dropout,
            bias=bias, add_bias_kv=add_bias_kv, add_zero_attn=add_zero_attn,
            data_type=data_type, kernel_initializer=kernel_initializer,
            apply_rotary_embedding=apply_rotary_embedding,
            scaling_query=scaling_query, scaling_factor=scaling_factor,
            qk_prod_scaling=qk_prod_scaling, position_bias=position_bias,
            rope_theta=rope_theta,
            max_requests=self.config.max_requests_per_batch,
            max_seq_length=self.config.max_sequence_length,
            use_pallas=self.config.use_pallas,
            cache_dtype=self.config.kv_cache_dtype,
            # only a model that has the step carries the key, so every
            # other model's attrs (and program) stay what they were
            **({} if qk_norm_eps is None else {"qk_norm_eps": qk_norm_eps}),
            **({"qk_norm_per_head": True} if qk_norm_per_head else {}),
            # a windowed layer: its ring holds the window plus the most
            # one step appends to a slot, the batch's token budget
            **({} if sliding_window is None else
               {"sliding_window": int(sliding_window),
                "max_step_tokens": self.config.max_tokens_per_batch}),
            # a block-diffusion model's layer: a query sees the keys of its
            # own block both ways (ops/inc_attention.block_visibility)
            **({} if block_length is None else
               {"block_length": int(block_length)}),
            # a chunked (EVA) layer: an exact tumbling window and one
            # learned summary pair a chunk of the positions before it
            # (ops/kv_layout.py ``chunked_*``)
            **({} if eva_window is None else
               {"eva_window": int(eva_window),
                "chunk_size": int(chunk_size)}),
            # ``W_o (sigmoid(W_g x) * Attn)``: a gate as wide as the heads
            **({"output_gate": True} if output_gate else {})),
            name)

    def _check_chunked(self, op_type, window, chunk, sliding_window,
                       block_length, position_bias):
        """What a chunked attention layer cannot be built with, each by its
        reason (the serving refusals over its cache are
        ops/inc_attention.refuse_windowed's)."""
        from flexflow_tpu.serve.request_manager import RequestManager

        if op_type != OpType.INC_MULTIHEAD_SELF_ATTENTION:
            raise NotImplementedError(
                "a chunked attention layer is served by incremental "
                f"decoding only, not as {op_type.name}: tree verification "
                "and beam drafting stage, move and roll back cache "
                "positions, and a position that has left the window "
                "survives only inside its chunk's summary")
        if (sliding_window is not None or block_length is not None
                or position_bias):
            raise NotImplementedError(
                "a chunked attention layer with a sliding window, a block "
                "length or a position bias: its visibility is its own (an "
                "exact window and the summaries before it)")
        cfg = self.config
        seg, _ = RequestManager._prefill_shape(cfg)
        if (not chunk or window % chunk or cfg.max_sequence_length % window
                or window % seg or seg % chunk):
            raise NotImplementedError(
                f"a chunked attention layer of window {window} and chunk "
                f"{chunk} under max_sequence_length "
                f"{cfg.max_sequence_length} and a prefill chunk of {seg} "
                "(max_tokens_per_batch over at most four rows): chunks "
                "tile the window, windows the slot, and a prefill segment "
                "is whole chunks inside ONE window (its queries share the "
                "window's rows and the summaries before it), so the "
                "chunk has to divide the prefill chunk and that the window")

    def inc_multihead_latent_attention(
            self, input: Tensor, embed_dim: int, num_heads: int,
            q_lora_rank: int, kv_lora_rank: int, qk_nope_head_dim: int,
            qk_rope_head_dim: int, v_head_dim: int, softmax_scale: float,
            rope_inv_freq, rope_theta: float = 10000.0,
            rope_factor: float = 1.0, pos_scale_beta: float = 0.0,
            pos_scale_period: int = 1, norm_eps: float = 1e-6,
            data_type: Optional[DataType] = None, kernel_initializer=None,
            name=None) -> Tensor:
        """Multi-head latent attention for incremental decoding
        (ops/latent_attention.py): the layer caches one shared entry a
        position, the normed ``kv_lora_rank`` latent and the rotated
        ``qk_rope_head_dim`` key part, and attends it in the absorbed form.
        ``rope_inv_freq``: the rotary frequency table (``qk_rope_head_dim /
        2`` numbers), times ``rope_factor`` on cos and sin;
        ``pos_scale_beta``/``pos_scale_period``: a query is scaled by ``1 +
        beta * ln(1 + floor(p / period))`` of its own position."""
        assert len(rope_inv_freq) * 2 == qk_rope_head_dim, (
            len(rope_inv_freq), qk_rope_head_dim)
        return self._add_layer(OpType.INC_MULTIHEAD_LATENT_ATTENTION, [input],
                               dict(
            embed_dim=embed_dim, num_q_heads=num_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            softmax_scale=float(softmax_scale),
            rope_inv_freq=tuple(float(f) for f in rope_inv_freq),
            rope_theta=rope_theta, rope_factor=float(rope_factor),
            pos_scale_beta=float(pos_scale_beta),
            pos_scale_period=int(pos_scale_period), norm_eps=norm_eps,
            data_type=data_type, kernel_initializer=kernel_initializer,
            max_requests=self.config.max_requests_per_batch,
            max_seq_length=self.config.max_sequence_length,
            use_pallas=self.config.use_pallas,
            cache_dtype=self.config.kv_cache_dtype), name)

    def inc_cca_attention(self, input: Tensor, embed_dim: int,
                          num_q_heads: int, num_kv_heads: int, head_dim: int,
                          rotary_dim: int, rope_theta: float = 10000.0,
                          data_type: Optional[DataType] = None,
                          kernel_initializer=None, name=None) -> Tensor:
        """Attention in a convolved latent for incremental decoding
        (ops/cca_attention.py, imported here: only a model that has such a
        layer loads it): ``num_q_heads`` query and ``num_kv_heads`` key/value
        heads of ``head_dim``, mixed along the sequence before they are
        normalised, rotated over their first ``rotary_dim`` dims and stored
        in a plain k/v cache; a slot keeps the row's tail beside it."""
        from flexflow_tpu.ops import cca_attention  # noqa: F401 (registers)

        assert num_q_heads % num_kv_heads == 0 and num_kv_heads % 2 == 0, (
            num_q_heads, num_kv_heads)
        assert 0 < rotary_dim <= head_dim and rotary_dim % 2 == 0, rotary_dim
        return self._add_layer(OpType.INC_MULTIHEAD_CCA_ATTENTION, [input],
                               dict(
            embed_dim=embed_dim, num_q_heads=num_q_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim,
            rotary_dim=int(rotary_dim), rope_theta=float(rope_theta),
            data_type=data_type, kernel_initializer=kernel_initializer,
            max_requests=self.config.max_requests_per_batch,
            max_seq_length=self.config.max_sequence_length,
            use_pallas=self.config.use_pallas,
            cache_dtype=self.config.kv_cache_dtype), name)

    def inc_kda_attention(self, input: Tensor, embed_dim: int,
                          num_heads: int, head_dim: int,
                          conv_kernel: int = 4, gate_rank: Optional[int] = None,
                          norm_eps: float = 1e-5,
                          data_type: Optional[DataType] = None,
                          kernel_initializer=None, name=None) -> Tensor:
        """Attention by a gated delta rule with one decay a key channel for
        incremental decoding (ops/kda_attention.py, imported here: only a
        model that has such a layer loads it): ``num_heads`` heads whose
        keys and values are ``head_dim`` wide, a depthwise causal
        convolution of ``conv_kernel`` taps on q, k and v, the decay and the
        output gate through ``gate_rank`` (default ``head_dim``). A slot
        keeps a recurrent state ``[num_heads, head_dim, head_dim]`` and the
        convolutions' tails, both float32; no cache of positions."""
        from flexflow_tpu.ops import kda_attention  # noqa: F401 (registers)

        return self._add_layer(OpType.INC_KDA_ATTENTION, [input], dict(
            embed_dim=embed_dim, num_heads=num_heads, head_dim=head_dim,
            conv_kernel=int(conv_kernel),
            gate_rank=int(gate_rank or head_dim), norm_eps=float(norm_eps),
            data_type=data_type, kernel_initializer=kernel_initializer,
            max_requests=self.config.max_requests_per_batch,
            use_pallas=self.config.use_pallas), name)

    def inc_ssd_mixer(self, input: Tensor, embed_dim: int, num_heads: int,
                      head_dim: int, state_dim: int, conv_kernel: int = 4,
                      norm_eps: float = 1e-5,
                      data_type: Optional[DataType] = None,
                      kernel_initializer=None, name=None) -> Tensor:
        """A state-space mixer (Mamba-2) for incremental decoding
        (ops/ssd_mixer.py, imported here: only a model that has such a
        layer loads it): ``num_heads`` heads of ``head_dim`` channels, each
        with one scalar decay a token, over ONE group of ``state_dim``
        input and output rows (``B``, ``C``) that all heads share; a
        depthwise causal convolution of ``conv_kernel`` taps with a bias; a
        skip term a head; the gate, then one RMSNorm over all ``num_heads *
        head_dim`` channels. A slot keeps a recurrent state ``[num_heads,
        head_dim, state_dim]`` and the convolution's tail, both float32; no
        cache of positions."""
        from flexflow_tpu.ops import ssd_mixer  # noqa: F401 (registers)

        return self._add_layer(OpType.INC_SSD_MIXER, [input], dict(
            embed_dim=embed_dim, num_heads=num_heads, head_dim=head_dim,
            state_dim=state_dim, conv_kernel=int(conv_kernel),
            norm_eps=float(norm_eps), data_type=data_type,
            kernel_initializer=kernel_initializer,
            max_requests=self.config.max_requests_per_batch,
            use_pallas=self.config.use_pallas), name)

    def inc_multihead_self_attention(self, input: Tensor, embed_dim: int,
                                     num_heads: int, **kw) -> Tensor:
        return self.inc_multiquery_self_attention(input, embed_dim, num_heads,
                                                  num_heads, **kw)

    def inc_multiquery_self_attention(
            self, input: Tensor, embed_dim: int, num_q_heads: int,
            num_kv_heads: int, kdim: int = 0, vdim: int = 0,
            dropout: float = 0.0, bias: bool = False,
            add_bias_kv: bool = False, add_zero_attn: bool = False,
            data_type: Optional[DataType] = None, kernel_initializer=None,
            apply_rotary_embedding: bool = False, scaling_query: bool = False,
            scaling_factor: float = 1.0, qk_prod_scaling: bool = True,
            position_bias: bool = False, rope_theta: float = 10000.0,
            name: Optional[str] = None,
            qk_norm_eps: Optional[float] = None,
            qk_norm_per_head: bool = False,
            sliding_window: Optional[int] = None,
            block_length: Optional[int] = None,
            eva_window: Optional[int] = None,
            chunk_size: Optional[int] = None,
            output_gate: bool = False) -> Tensor:
        """``output_gate``: the heads' output times ``sigmoid(W_g x)``,
        elementwise, before the output projection (a weight ``wg``).
        ``eva_window``, ``chunk_size``: a chunked (EVA) layer: a query
        sees its own window of ``eva_window`` positions exactly and one
        learned summary pair for every ``chunk_size`` positions of the
        windows before, in one softmax, from a cache that keeps both
        (ops/kv_layout.py ``chunked_*``).
        ``qk_norm_eps``: RMS-normalise q and k, over the whole projection
        or (``qk_norm_per_head``) over each head. ``sliding_window``: a
        query sees the last that many positions, and the layer keeps a ring
        of them instead of ``max_sequence_length`` (ops/kv_layout.py).
        ``block_length``: a block-diffusion model's layer: causal across
        blocks of that many positions, both ways inside one."""
        return self._serving_attention(
            OpType.INC_MULTIHEAD_SELF_ATTENTION, input, embed_dim, num_q_heads,
            num_kv_heads, kdim, vdim, dropout, bias, add_bias_kv,
            add_zero_attn, data_type, kernel_initializer,
            apply_rotary_embedding, scaling_query, scaling_factor,
            qk_prod_scaling, position_bias, rope_theta, name, qk_norm_eps,
            qk_norm_per_head, sliding_window, block_length, eva_window,
            chunk_size, output_gate)

    def spec_inc_multihead_self_attention(self, input: Tensor, embed_dim: int,
                                          num_heads: int, **kw) -> Tensor:
        return self.spec_inc_multiquery_self_attention(
            input, embed_dim, num_heads, num_heads, **kw)

    def spec_inc_multiquery_self_attention(
            self, input: Tensor, embed_dim: int, num_q_heads: int,
            num_kv_heads: int, kdim: int = 0, vdim: int = 0,
            dropout: float = 0.0, bias: bool = False,
            add_bias_kv: bool = False, add_zero_attn: bool = False,
            data_type: Optional[DataType] = None, kernel_initializer=None,
            apply_rotary_embedding: bool = False, scaling_query: bool = False,
            scaling_factor: float = 1.0, qk_prod_scaling: bool = True,
            position_bias: bool = False, rope_theta: float = 10000.0,
            name: Optional[str] = None,
            qk_norm_eps: Optional[float] = None) -> Tensor:
        return self._serving_attention(
            OpType.SPEC_INC_MULTIHEAD_SELF_ATTENTION, input, embed_dim,
            num_q_heads, num_kv_heads, kdim, vdim, dropout, bias, add_bias_kv,
            add_zero_attn, data_type, kernel_initializer,
            apply_rotary_embedding, scaling_query, scaling_factor,
            qk_prod_scaling, position_bias, rope_theta, name, qk_norm_eps)

    def tree_inc_multihead_self_attention(self, input: Tensor, embed_dim: int,
                                          num_heads: int, **kw) -> Tensor:
        return self.tree_inc_multiquery_self_attention(
            input, embed_dim, num_heads, num_heads, **kw)

    def tree_inc_multiquery_self_attention(
            self, input: Tensor, embed_dim: int, num_q_heads: int,
            num_kv_heads: int, kdim: int = 0, vdim: int = 0,
            dropout: float = 0.0, bias: bool = False,
            add_bias_kv: bool = False, add_zero_attn: bool = False,
            data_type: Optional[DataType] = None, kernel_initializer=None,
            apply_rotary_embedding: bool = False, scaling_query: bool = False,
            scaling_factor: float = 1.0, qk_prod_scaling: bool = True,
            position_bias: bool = False, rope_theta: float = 10000.0,
            name: Optional[str] = None,
            qk_norm_eps: Optional[float] = None) -> Tensor:
        return self._serving_attention(
            OpType.TREE_INC_MULTIHEAD_SELF_ATTENTION, input, embed_dim,
            num_q_heads, num_kv_heads, kdim, vdim, dropout, bias, add_bias_kv,
            add_zero_attn, data_type, kernel_initializer,
            apply_rotary_embedding, scaling_query, scaling_factor,
            qk_prod_scaling, position_bias, rope_theta, name, qk_norm_eps)

    # --- elementwise binary ---
    def add(self, x, y, name=None):
        return self._add_layer(OpType.EW_ADD, [x, y], {}, name)

    def subtract(self, x, y, name=None):
        return self._add_layer(OpType.EW_SUB, [x, y], {}, name)

    def multiply(self, x, y, name=None):
        return self._add_layer(OpType.EW_MUL, [x, y], {}, name)

    def divide(self, x, y, name=None):
        return self._add_layer(OpType.EW_DIV, [x, y], {}, name)

    def max(self, x, y, name=None):
        return self._add_layer(OpType.EW_MAX, [x, y], {}, name)

    def min(self, x, y, name=None):
        return self._add_layer(OpType.EW_MIN, [x, y], {}, name)

    # --- elementwise unary ---
    def relu(self, x, name=None):
        return self._add_layer(OpType.RELU, [x], {}, name)

    def sigmoid(self, x, name=None):
        return self._add_layer(OpType.SIGMOID, [x], {}, name)

    def tanh(self, x, name=None):
        return self._add_layer(OpType.TANH, [x], {}, name)

    def elu(self, x, name=None):
        return self._add_layer(OpType.ELU, [x], {}, name)

    def gelu(self, x, approximate: bool = False, name=None):
        """Exact (erf) by default — HF torch.nn.GELU parity; tanh form via
        approximate=True (gelu_pytorch_tanh, used by StarCoder)."""
        return self._add_layer(OpType.GELU, [x],
                               dict(approximate=approximate), name)

    def identity(self, x, name=None):
        return self._add_layer(OpType.IDENTITY, [x], {}, name)

    def exp(self, x, name=None):
        return self._add_layer(OpType.EXP, [x], {}, name)

    def sin(self, x, name=None):
        return self._add_layer(OpType.SIN, [x], {}, name)

    def cos(self, x, name=None):
        return self._add_layer(OpType.COS, [x], {}, name)

    def rsqrt(self, x, name=None):
        return self._add_layer(OpType.RSQRT, [x], {}, name)

    def pow(self, x, exponent: float, name=None):
        return self._add_layer(OpType.POW, [x], dict(exponent=exponent), name)

    def scalar_multiply(self, x, scalar: float, inplace: bool = True, name=None):
        return self._add_layer(OpType.SCALAR_MULTIPLY, [x],
                               dict(scalar=scalar), name)

    def scalar_add(self, x, scalar: float, inplace: bool = True, name=None):
        return self._add_layer(OpType.SCALAR_ADD, [x], dict(scalar=scalar), name)

    def scalar_sub(self, x, scalar: float, inplace: bool = True, name=None):
        return self._add_layer(OpType.SCALAR_SUB, [x], dict(scalar=scalar), name)

    def scalar_true_divide(self, x, scalar: float, inplace: bool = True, name=None):
        return self._add_layer(OpType.SCALAR_TRUE_DIV, [x],
                               dict(scalar=scalar), name)

    # --- shape ---
    def concat(self, tensors: List[Tensor], axis: int, name=None):
        return self._add_layer(OpType.CONCAT, list(tensors), dict(axis=axis), name)

    def split(self, input: Tensor, sizes, axis: int, name=None):
        if isinstance(sizes, int):
            sizes = [input.dims[axis] // sizes] * sizes
        return self._add_layer(OpType.SPLIT, [input],
                               dict(sizes=list(sizes), axis=axis), name)

    def reshape(self, input: Tensor, shape: Sequence[int], name=None):
        return self._add_layer(OpType.RESHAPE, [input],
                               dict(shape=tuple(shape)), name)

    def transpose(self, input: Tensor, perm: Sequence[int], name=None):
        return self._add_layer(OpType.TRANSPOSE, [input],
                               dict(perm=tuple(perm)), name)

    def reverse(self, input: Tensor, axis: int, name=None):
        return self._add_layer(OpType.REVERSE, [input], dict(axis=axis), name)

    def flat(self, input: Tensor, name=None):
        return self._add_layer(OpType.FLAT, [input], {}, name)

    def slice_tensor(self, input: Tensor, starts, ends,
                     squeeze_dims=(), name=None):
        """Static slice; starts/ends per dim (None = full extent, negatives
        wrap); squeeze_dims drop sliced size-1 dims (BERT's x[:, 0])."""
        return self._add_layer(OpType.SLICE, [input], dict(
            starts=tuple(starts), ends=tuple(ends),
            squeeze_dims=tuple(squeeze_dims)), name)

    def squeeze(self, input: Tensor, dim: int, name=None):
        dim = dim % input.num_dims
        assert input.dims[dim] == 1, (input.dims, dim)
        shape = [s for d, s in enumerate(input.dims) if d != dim]
        return self.reshape(input, shape, name=name)

    def unsqueeze(self, input: Tensor, dim: int, name=None):
        shape = list(input.dims)
        dim = dim % (input.num_dims + 1)
        shape.insert(dim, 1)
        return self.reshape(input, shape, name=name)

    def cast(self, input: Tensor, dtype: DataType, name=None):
        return self._add_layer(OpType.CAST, [input], dict(dtype=dtype), name)

    # --- algebra / reductions ---
    def softmax(self, input: Tensor, axis: int = -1, name=None):
        return self._add_layer(OpType.SOFTMAX, [input], dict(axis=axis), name)

    def batch_matmul(self, a: Tensor, b: Tensor, name=None):
        return self._add_layer(OpType.BATCH_MATMUL, [a, b], {}, name)

    def reduce_sum(self, input: Tensor, axes, keepdims: bool = False, name=None):
        return self._add_layer(OpType.REDUCE_SUM, [input],
                               dict(axes=tuple(axes), keepdims=keepdims), name)

    def reduce_mean(self, input: Tensor, axes, keepdims: bool = False, name=None):
        return self._add_layer(OpType.REDUCE_MEAN, [input],
                               dict(axes=tuple(axes), keepdims=keepdims), name)

    def mean(self, input: Tensor, dims, keepdims: bool = False, name=None):
        return self._add_layer(OpType.MEAN, [input],
                               dict(dims=tuple(dims), keepdims=keepdims), name)

    def gather(self, input: Tensor, index: Tensor, dim: int, name=None):
        return self._add_layer(OpType.GATHER, [input, index], dict(dim=dim), name)

    # --- constants / selection (torch-frontend lowering targets) ---
    def constant_tensor(self, value, dtype: Optional[DataType] = None,
                        name=None):
        """Embedded literal tensor (folded constants from traced graphs)."""
        arr = np.asarray(value)
        if dtype is None:
            dtype = DataType.from_jnp(arr.dtype)
        else:
            arr = arr.astype(dtype.to_jnp())
        return self._add_layer(OpType.CONSTANT, [],
                               dict(value=arr.tolist(), dtype=dtype.value,
                                    shape=list(arr.shape)), name)

    def parameter(self, dims: Sequence[int],
                  dtype: DataType = DataType.DT_FLOAT, init: float = 1.0,
                  name=None, initializer=None):
        """Free-standing trainable parameter (reference PCG Weight node) —
        e.g. a bare nn.Parameter read in a traced torch module. Starts at
        the constant ``init``, or as ``initializer`` makes it."""
        return self._add_layer(
            OpType.WEIGHT, [],
            dict(shape=list(dims), dtype=dtype.value, init=init,
                 **({} if initializer is None
                    else {"initializer": initializer})), name)

    def where(self, cond: Tensor, x: Tensor, y: Tensor, name=None):
        return self._add_layer(OpType.WHERE, [cond, x, y], {}, name)

    def compare(self, x: Tensor, other, cmp: str, name=None):
        """Elementwise comparison; ``other`` is a Tensor or a scalar."""
        if isinstance(other, Tensor):
            return self._add_layer(OpType.COMPARE, [x, other],
                                   dict(cmp=cmp), name)
        return self._add_layer(OpType.COMPARE, [x],
                               dict(cmp=cmp, scalar=float(other)), name)

    def broadcast_to(self, input: Tensor, shape: Sequence[int], name=None):
        return self._add_layer(OpType.BROADCAST_TO, [input],
                               dict(shape=list(shape)), name)

    def top_k(self, input: Tensor, k: int, sorted: bool = True, name=None):
        return self._add_layer(OpType.TOPK, [input], dict(k=k, sorted=sorted), name)

    def arg_top_k(self, input: Tensor, k: int, sorted: bool = True,
                  speculative_decoding: bool = False, name=None):
        return self._add_layer(OpType.ARG_TOPK, [input], dict(
            k=k, sorted=sorted, speculative_decoding=speculative_decoding), name)

    def argmax(self, input: Tensor, beam_search: bool = False, name=None):
        return self._add_layer(OpType.ARGMAX, [input],
                               dict(beam_search=beam_search), name)

    def unmasking_head(self, logits: Tensor, diffusion, name=None):
        """The head of a block-diffusion model: at every position the
        greedy pick and its float32 softmax probability, two outputs
        (ops/sampling_ops.ArgMax ``confidence``). ``diffusion`` (a
        serve/batch_config.BlockDiffusion) is how the serving stack fills
        a block from them; compile hands it on as ``self.block_diffusion``.
        Returns the picks."""
        return self._add_layer(
            OpType.ARGMAX, [logits],
            dict(beam_search=False, confidence=True, diffusion=diffusion),
            name)

    def sampling(self, input: Tensor, top_p: float = 1.0,
                 temperature: float = 1.0, name=None):
        return self._add_layer(OpType.SAMPLING, [input],
                               dict(top_p=top_p, temperature=temperature), name)

    def beam_top_k(self, input: Tensor, max_beam_width: int,
                   sorted: bool = True, name=None):
        return self._add_layer(OpType.BEAM_TOPK, [input],
                               dict(max_beam_width=max_beam_width,
                                    sorted=sorted), name)

    # --- MoE ---
    def group_by(self, data: Tensor, assign: Tensor, n: int, alpha: float = 1.0,
                 name=None):
        k = assign.dims[-1]
        return self._add_layer(OpType.GROUP_BY, [data, assign],
                               dict(n=n, k=k, alpha=alpha), name)

    def aggregate(self, gate_preds: Tensor, gate_assign: Tensor,
                  exp_preds: List[Tensor], n: int, lambda_bal: float = 0.0,
                  name=None):
        return self._add_layer(OpType.AGGREGATE,
                               [gate_preds, gate_assign] + list(exp_preds),
                               dict(n=n, lambda_bal=lambda_bal), name)

    def aggregate_spec(self, gate_preds: Tensor, gate_assign: Tensor,
                       exp_preds: List[Tensor], n: int, lambda_bal: float = 0.0,
                       name=None):
        return self._add_layer(OpType.AGG_SPEC,
                               [gate_preds, gate_assign] + list(exp_preds),
                               dict(n=n, lambda_bal=lambda_bal), name)

    def experts(self, input: Tensor, indices: Tensor, gate_weights: Tensor,
                num_experts: int, experts_start_idx: int,
                experts_output_dim_size: int,
                experts_num_layers: int = 1,
                experts_internal_dim_size: int = 0,
                activation: ActiMode = ActiMode.AC_MODE_NONE,
                use_bias: bool = False, name=None):
        return self._add_layer(OpType.EXPERTS, [input, indices, gate_weights],
                               dict(num_experts=num_experts,
                                    experts_start_idx=experts_start_idx,
                                    experts_output_dim_size=experts_output_dim_size,
                                    experts_num_layers=experts_num_layers,
                                    experts_internal_dim_size=experts_internal_dim_size,
                                    activation=activation, use_bias=use_bias), name)

    def moe_experts(self, input: Tensor, indices: Tensor, weights: Tensor,
                    num_experts: int, expert_width: int,
                    data_type: Optional[DataType] = None, name=None,
                    held: Optional[Tuple[int, int]] = None,
                    zero_experts: Optional[Tuple[int, int]] = None):
        """The serving path's routed SwiGLU experts (ops/moe.MoeExperts):
        dropless, over the step's real tokens, through the grouped kernel.
        ``indices``/``weights`` are the router's top-k over ``num_experts``.
        ``held`` ``(first, count)``: this chip's share of an expert-parallel
        layer. It holds experts ``[first, first + count)`` of the router's
        ``num_experts`` and computes their part of the result; a pair routed
        elsewhere is no work here. Without it the layer holds them all.
        ``zero_experts`` ``(first, count)``: indices of the router's
        ``num_experts`` that name no expert anywhere (outside ``held``): a
        pick of one adds ``weight * input`` and costs no expert's
        arithmetic."""
        attrs = dict(num_experts=num_experts, expert_width=expert_width,
                     data_type=data_type)
        if zero_experts is not None:
            assert held is not None, "say which of the indices are experts"
            (z0, zn), (h0, hn) = zero_experts, held
            assert 0 <= z0 and z0 + zn <= num_experts and (
                h0 + hn <= z0 or z0 + zn <= h0), (held, zero_experts)
            attrs["zero_experts"] = (int(z0), int(zn))
        if held is not None and tuple(held) != (0, num_experts):
            first, count = held
            assert 0 <= first and first + count <= num_experts, held
            attrs.update(num_experts=count, router_width=num_experts,
                         first_expert=first)
        return self._add_layer(OpType.MOE_EXPERTS, [input, indices, weights],
                               attrs, name)

    def cache(self, input: Tensor, num_batches: int = 1, name=None):
        """Cross-batch activation cache with staleness score (reference
        src/ops/cache.cc; pairs with RecompileState for adaptive MoE)."""
        return self._add_layer(OpType.CACHE, [input],
                               dict(num_batches=num_batches), name)

    def get_cache_score(self, layer_name: str) -> float:
        """Host-side read of a Cache op's staleness score (reference
        cache.cc score trigger feeding recompile decisions)."""
        st = (self.op_state or {}).get(layer_name)
        if st is None or "score" not in st:
            raise KeyError(f"no cache state for layer {layer_name!r}")
        return float(st["score"])

    def moe(self, input: Tensor, num_exp: int, num_select: int,
            expert_hidden_size: int, alpha: float = 2.0, lambda_bal: float = 0.0):
        """Composite MoE layer (reference src/ops/moe.cc:44
        FFModel::moe = topk + groupby + experts + aggregate)."""
        gate = self.dense(input, num_exp, ActiMode.AC_MODE_NONE)
        gate = self.softmax(gate)
        topk_out = self.top_k(gate, num_select)
        values, assign = topk_out
        buckets = self.group_by(input, assign, num_exp, alpha)
        if not isinstance(buckets, list):
            buckets = [buckets]
        outs = []
        for b in buckets:
            h = self.dense(b, expert_hidden_size, ActiMode.AC_MODE_RELU)
            outs.append(self.dense(h, input.dims[-1]))
        return self.aggregate(values, assign, outs, num_exp, lambda_bal)

    # --- parallel ops (reference src/parallel_ops/; sharding boundaries) ---
    def repartition(self, input: Tensor, repartition_dim: int,
                    repartition_degree: int = 0, axis_name: str = "data",
                    name=None):
        return self._add_layer(OpType.REPARTITION, [input],
                               dict(repartition_dim=repartition_dim,
                                    repartition_degree=repartition_degree,
                                    axis_name=axis_name), name)

    def combine(self, input: Tensor, combine_dim: int = 0,
                combine_degree: int = 0, name=None):
        return self._add_layer(OpType.COMBINE, [input],
                               dict(combine_dim=combine_dim,
                                    combine_degree=combine_degree), name)

    def replicate(self, input: Tensor, replicate_dim: int = 0,
                  replicate_degree: int = 0, name=None):
        return self._add_layer(OpType.REPLICATE, [input],
                               dict(replicate_dim=replicate_dim,
                                    replicate_degree=replicate_degree), name)

    def reduction(self, input: Tensor, reduction_dim: int = 0,
                  reduction_degree: int = 0, name=None):
        return self._add_layer(OpType.REDUCTION, [input],
                               dict(reduction_dim=reduction_dim,
                                    reduction_degree=reduction_degree), name)

    def allreduce(self, input: Tensor, name=None):
        return self._add_layer(OpType.ALLREDUCE, [input], {}, name)

    # ---- a loop region (ops/loop.py) ----
    def loop_begin(self, input: Tensor, steps: int, name=None) -> Tensor:
        """Open a LOOP REGION: the layers recorded from here to
        ``loop_end`` run ``steps`` times over their one set of weights,
        inside one device loop. Returns the span's input: ``input`` in the
        first pass, what the pass before handed on in every later one."""
        if any(r.end is None for r in self._loop_regions):
            raise NotImplementedError(
                "a loop region inside a loop region: close the open one "
                "(loop_end) first")
        if int(steps) < 1:
            raise ValueError(f"a loop region runs at least once, not {steps}")
        out = self._add_layer(OpType.LOOP_BEGIN, [input],
                              {"steps": int(steps)}, name)
        self._loop_regions.append(LoopRegion(self.layers[-1]))
        return out

    def loop_end(self, input: Tensor, collect: Sequence[Tensor] = (),
                 name=None) -> List[Tensor]:
        """Close the open loop region: ``input`` (the shape and dtype of
        the span's input) is what a pass hands the next. Returns
        ``[last, *stacks]``: the last pass's ``input``, then every pass's
        value of each tensor of ``collect``, ``[steps, ...]``."""
        region = self._loop_regions[-1] if self._loop_regions else None
        if region is None or region.end is not None:
            raise ValueError("loop_end without an open loop_begin")
        begin = region.begin
        if (input.dims, input.dtype) != (begin.outputs[0].dims,
                                         begin.outputs[0].dtype):
            raise ValueError(
                "a pass hands the next what the span was given: "
                f"{begin.outputs[0].dims} {begin.outputs[0].dtype}, not "
                f"{input.dims} {input.dtype}")
        first = self.layers.index(begin) + 1
        region.span = self.layers[first:]
        outs = self._add_layer(OpType.LOOP_END, [input, *collect],
                               {"steps": begin.attrs["steps"]}, name)
        region.end = self.layers[-1]
        return outs if isinstance(outs, list) else [outs]

    def loop_exit(self, states: Tensor, gates: Tensor, threshold: float,
                  name=None) -> Tensor:
        """A looped model's exit rule (ops/loop.LoopExit): of every pass's
        ``states`` [steps, R, Q, E], each token's behind the first pass at
        which the running sum of its gate's exit probabilities (``gates``
        [steps, R, Q, 1], logits) reaches ``threshold``, else the last."""
        return self._add_layer(OpType.LOOP_EXIT, [states, gates],
                               {"threshold": float(threshold)}, name)

    # ==================================================================
    # Graph execution
    # ==================================================================
    def _apply_layer(self, layer, params, values: Dict[int, Any],
                     ctx: OpContext):
        """Execute one layer into ``values`` (offload fetch, lazy dequant,
        searched-layout constraint)."""
        from flexflow_tpu.offload import fetch_layer_params
        from flexflow_tpu.quant import dequantize_layer_params

        offloaded = getattr(self, "_offloaded", None) or {}
        impl = get_op_impl(layer.op_type)
        ins = [values[t.tensor_id] for t in layer.inputs]
        ctx.layer_name = layer.name
        # host-offloaded weights stream back to HBM first (in their
        # compressed form), then int8/int4 dequantizes lazily — all
        # inside the jitted step so XLA overlaps transfer with compute
        lp = params.get(layer.name, {})
        if "tied_to" in layer.attrs:    # a head on the embedding's table
            lp = {**lp, "table": params[layer.attrs["tied_to"]]["weight"]}
        if layer.name in offloaded:
            lp = fetch_layer_params(lp, offloaded[layer.name])
        if not impl.quant_aware:
            lp = dequantize_layer_params(lp, ctx.compute_dtype)
        # compile-time metadata only: the layer's operations carry
        # "<op_type>/<layer name>" in a device trace (README "Telemetry")
        with jax.named_scope(f"{layer.op_type.name.lower()}/{layer.name}"):
            outs = impl.forward(layer.attrs, lp, ins, ctx)
        if self.strategy is not None and self.policy is not None:
            strat_op = self.strategy.ops.get(layer.name)
            if strat_op is not None and outs:
                outs = [self.policy.constrain(outs[0],
                                              strat_op.output_spec),
                        *outs[1:]]
        for t, v in zip(layer.outputs, outs):
            values[t.tensor_id] = v

    def _run_graph(self, params, feeds: Dict[int, Any], ctx: OpContext,
                   state: Optional[Dict[str, Any]] = None, narrow=None):
        """Walk the layer list (creation order == topo order) computing every
        tensor value. Returns (values_by_tensor_id, new_state). ``narrow``
        (a layer, a function): that layer, and so whatever follows it, is
        given the function of its inputs' values."""
        if (not ctx.training and self._pp_plan is not None
                and "__pp_blocks__" in params):
            from flexflow_tpu.serve.pipeline_plan import run_pp_graph

            assert narrow is None, "a pipeline stage runs whole layers"
            return run_pp_graph(self, params, feeds, ctx, state)
        values: Dict[int, Any] = dict(feeds)
        ctx.state_in = state or {}
        ctx.state_out = {}
        plan = getattr(self, "_branch_plan", None)
        loop = self.loop_region
        for layer in self.layers:
            if loop is not None and layer in loop.inside:
                if layer is loop.begin:     # the span, every pass of it
                    self._run_loop(loop, params, values, ctx)
                continue
            if narrow is not None and layer is narrow[0]:
                for t in layer.inputs:
                    values[t.tensor_id] = narrow[1](values[t.tensor_id])
            if plan is not None:
                if layer.name in plan.skip:
                    continue            # executed inside its branch region
                region = plan.by_join.get(layer.name)
                if region is not None:
                    from flexflow_tpu.core.branch_exec import \
                        run_branch_region

                    if run_branch_region(self, region, params, values, ctx):
                        continue        # join output written by the region
                    # runtime fallback (e.g. batch not splittable): run
                    # the deferred branch layers sequentially, then the
                    # join itself below
                    for chain in region.chains:
                        for ly in chain:
                            self._apply_layer(ly, params, values, ctx)
            self._apply_layer(layer, params, values, ctx)
        new_state = dict(ctx.state_in)
        new_state.update(ctx.state_out)
        return values, new_state

    def _run_loop(self, region, params, values: Dict[int, Any],
                  ctx: OpContext):
        """Run a loop region: ONE ``lax.scan`` over the passes whose body
        is the span, once. It carries the hidden value and the op state
        (the stacked caches go through it in place, as through the decode
        block's own loop); the parameters are the body's constants, read
        once a layer; the pass index reaches the ops as ``ctx.loop_step``.
        Writes the outputs of the region's end into ``values``."""
        from flexflow_tpu.ops.loop import count_pass

        begin, end = region.begin, region.end
        h0 = values[begin.inputs[0].tensor_id]
        outer_in, outer_out = ctx.state_in, ctx.state_out
        written = set()

        def one_pass(carry, step):
            h, state = carry
            vals = dict(values)         # what the span reads from before it
            vals[begin.outputs[0].tensor_id] = h
            ctx.state_in, ctx.state_out, ctx.loop_step = state, {}, step
            for layer in region.span:
                self._apply_layer(layer, params, vals, ctx)
            count_pass(ctx, h, len(region.cache_layers))   # telemetry on
            new = set(ctx.state_out) - set(state)
            assert not new, f"a pass may not add op state: {sorted(new)}"
            written.update(ctx.state_out)
            out = vals[end.inputs[0].tensor_id]
            assert (out.shape, out.dtype) == (h.shape, h.dtype), (
                f"a pass hands on {out.shape} {out.dtype}, the span takes "
                f"{h.shape} {h.dtype}")
            return ((out, {**state, **ctx.state_out}),
                    tuple(vals[t.tensor_id] for t in end.inputs[1:]))

        try:
            with jax.named_scope(f"loop_region/{begin.name}"):
                (h, state), stacks = jax.lax.scan(
                    one_pass, (h0, {**outer_in, **outer_out}),
                    jnp.arange(region.steps, dtype=jnp.int32))
        finally:
            ctx.state_in, ctx.state_out, ctx.loop_step = (outer_in,
                                                          outer_out, None)
        ctx.state_out.update({k: state[k] for k in written})
        for t, v in zip(end.outputs, (h, *stacks)):
            values[t.tensor_id] = v

    def _note_loop_region(self, split):
        """``self.loop_region``: the model's LoopRegion, None for every
        model without one. Refuses here what a region cannot hold or be
        served through (ops/loop.py)."""
        from flexflow_tpu.ops.loop import refuse_looped

        self.loop_region = None
        if not self._loop_regions:
            return
        if len(self._loop_regions) > 1 or self._loop_regions[0].end is None:
            raise NotImplementedError(
                "one closed loop region a model: "
                f"{len(self._loop_regions)} were begun")
        region = self.loop_region = self._loop_regions[0]
        region.check(self)
        if self.config.pipeline_parallelism_degree > 1:
            refuse_looped(self, "a pipeline plan (serve/pipeline_plan.py: a "
                          "stage is a run of layers used once)")
        if split:
            refuse_looped(self, f"a mesh that divides a model ({split})")
        if self.config.inference_debugging:
            refuse_looped(self, "inference_debugging (its dump walks the "
                          "layer list, a layer a step)")

    # ==================================================================
    # Compile
    # ==================================================================
    def compile(self, optimizer=None, loss_type: Optional[LossType] = None,
                metrics: Optional[List[MetricsType]] = None,
                comp_mode: CompMode = CompMode.COMP_MODE_TRAINING):
        """Lower the layer graph into jitted step functions over the mesh.

        Reference: FFModel::compile (model.cc:3304) — Layer->Op lowering, the
        Unity search for MachineViews, region allocation, fusion, NCCL setup.
        Here: mesh construction, parameter init with NamedShardings, and
        jit of train/eval steps (XLA handles fusion and collectives).
        """
        self.optimizer = optimizer
        self.loss_type = loss_type
        self.metrics = list(metrics or [])
        self.comp_mode = comp_mode

        self.mesh = make_mesh(self.config)
        self.policy = ShardingPolicy(self.mesh)
        self._pp_plan = None
        self._pp_segment_fn = None

        # --- Unity-style auto-parallelization (reference model.cc:3327
        # launches GRAPH_OPTIMIZE_TASK inside compile). A strategy the
        # user assigned BEFORE compile (manual per-op shardings, e.g. a
        # Strategy.load of an exported search result) is kept: it drives
        # weight placement at init and the run-graph constraints below.
        if self.config.auto_parallel:
            from flexflow_tpu.search import optimize_model

            self.strategy = optimize_model(
                self, chip=self.config.tpu_chip,
                training=(comp_mode == CompMode.COMP_MODE_TRAINING))
        if (self.strategy is not None
                and self.strategy.axis_degrees is not None):
            # the search explored mesh factorizations (search_mesh) and a
            # different one won: adopt its degrees and rebuild the mesh
            deg = self.strategy.axis_degrees
            self.config.data_parallelism_degree = deg.get("data", 1)
            self.config.tensor_parallelism_degree = deg.get("model", 1)
            self.config.expert_parallelism_degree = deg.get("expert", 1)
            self.config.sequence_parallelism_degree = deg.get("seq", 1)
            self.mesh = make_mesh(self.config)
            self.policy = ShardingPolicy(self.mesh)
        if self.config.export_strategy_file:
            # dot export of the (searched) computation graph (reference
            # --export-strategy-computation-graph-file, model.cc:4218)
            from flexflow_tpu.utils.dot import export_model_dot

            costs = None
            if self.config.include_costs_dot_graph:
                costs = self._estimate_layer_costs()
            export_model_dot(
                self, self.config.export_strategy_file,
                include_costs=self.config.include_costs_dot_graph,
                costs=costs, strategy=self.strategy)

        # --- parameter + op-state init ---
        key = jax.random.PRNGKey(self.config.seed)
        params: Dict[str, Dict[str, jnp.ndarray]] = {}
        quantize = bool(self.config.quantization_type
                        and comp_mode == CompMode.COMP_MODE_INFERENCE)
        if quantize:
            from flexflow_tpu.quant import quantize_params
        for layer in self.layers:
            if not layer.weights:
                continue
            lp = {}
            strat_op = (self.strategy.ops.get(layer.name)
                        if self.strategy is not None else None)
            for w in layer.weights:
                wkey = jax.random.fold_in(
                    key, stable_hash(layer.name, w.name))
                arr = w.initializer(wkey, w.shape, w.dtype.to_jnp())
                wdims = w.sharding_dims
                if strat_op is not None and w.name in strat_op.weight_specs:
                    wdims = strat_op.weight_specs[w.name]
                sharding = self.policy.weight_sharding(
                    w.shape, wdims, w.shard_multiples)
                lp[w.name] = jax.device_put(arr, sharding)
                if quantize and "router_width" in layer.attrs:
                    # a chip's share of an expert-parallel layer fills the
                    # chip: one weight at a time, and wait for it. The
                    # host runs ahead of the device, and every temporary
                    # of the unfused quantisation of every weight it has
                    # queued is allocated at once (seen on the chip: a
                    # 3.1 GB cut peaked at 12.5 GB, 13.0 GB at all 16.27)
                    lp[w.name] = jax.block_until_ready(quantize_params(
                        {layer.name: {w.name: lp[w.name]}},
                        self.config.quantization_type)[layer.name][w.name])
            if quantize:
                # quantize each layer as it is initialized (the reference
                # also compresses at load time, per tensor) — peak HBM
                # holds ONE full-precision layer, so a 7B-class model can
                # be built int8/int4 on a chip its bf16 form wouldn't fit
                # (a weight quantized above is left as it is)
                lp = quantize_params({layer.name: lp},
                                     self.config.quantization_type
                                     )[layer.name]
            params[layer.name] = lp
        self.params = params

        split = {a: n for a, n in self.mesh.shape.items()
                 if a != "data" and n > 1}
        self._note_loop_region(split)
        self.op_state = {}
        for layer in self.layers:
            impl = get_op_impl(layer.op_type)
            if hasattr(impl, "init_state"):
                input_specs = [(t.dims, t.dtype) for t in layer.inputs]
                self.op_state[layer.name] = impl.init_state(layer.attrs,
                                                            input_specs)
        self._consolidate_kv_caches()
        if split:
            from flexflow_tpu.ops.inc_attention import refuse_windowed

            refuse_windowed(self.op_state,
                            f"a mesh that divides a model ({split})")
        self._note_block_diffusion(split)
        from flexflow_tpu.ops.loop import init_counters as init_loop_counters
        from flexflow_tpu.ops.moe import init_counters

        init_counters(self)     # routed-expert layers, telemetry on
        init_loop_counters(self)    # a loop region, likewise
        # --- pipeline-parallel serving plan (reference
        # inference_manager.cc:91-132 layer->stage placement); built after
        # KV consolidation so blocks carry their cache_layer_idx ---
        if (comp_mode == CompMode.COMP_MODE_INFERENCE
                and "pipe" in self.mesh.shape and self.mesh.shape["pipe"] > 1):
            from flexflow_tpu.serve.pipeline_plan import build_pipeline_plan

            self._pp_plan = build_pipeline_plan(self,
                                                self.mesh.shape["pipe"])
            if self._pp_plan is None:
                raise ValueError(
                    "pipeline_parallelism_degree > 1 needs a homogeneous "
                    "transformer-block serving graph (model-zoo style "
                    "'<prefix>.{i}.' layer naming, num_layers divisible by "
                    "the degree); this graph has no such decomposition")
        # Commit op-state (KV caches) to the mesh NOW: jit caches key on
        # argument shardings, so uncommitted zeros here would make the first
        # post-warmup call recompile every serving program once the donated
        # outputs come back with concrete placements.
        # KV caches additionally shard their S dim over a "seq" mesh axis
        # (searched sequence-parallel plans — each device then holds S/deg
        # cache rows and attention runs seq_sharded_attend).
        def _commit_state(path, x):
            name = ""
            for p in reversed(path):
                key = getattr(p, "key", None)
                if isinstance(key, str):
                    name = key
                    break
            if (name in ("k_cache", "v_cache", "k", "v")
                    and getattr(x, "ndim", 0) >= 4):
                return jax.device_put(
                    x, self.policy.kv_cache_sharding(x.shape))
            return jax.device_put(x, self.policy.replicated())

        self.op_state = jax.tree_util.tree_map_with_path(
            _commit_state, self.op_state)

        # --- branch-parallel (nonsequence split) execution plan: turn the
        # searched OpStrategy.branch tags into shard_map regions so the
        # split is executed, not just annotated (core/branch_exec.py) ---
        from flexflow_tpu.core.branch_exec import build_branch_plan

        self._branch_plan = build_branch_plan(self)

        # --- label tensor (reference compile creates it from final output) ---
        final = self.layers[-1].outputs[0] if self.layers else None
        self._final_tensor = final
        self._logits_tensor = None
        if final is not None and self.layers[-1].op_type == OpType.SOFTMAX:
            self._logits_tensor = self.layers[-1].inputs[0]
        if final is not None and self.label_tensor is None:
            if loss_type in (LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,):
                lshape = (final.dims[0], 1)
                ldtype = DataType.DT_INT32
            else:
                lshape = final.dims
                ldtype = final.dtype
            self.label_tensor = Tensor(lshape, ldtype, name="label", model=self)

        if optimizer is not None:
            # Back-reference so optimizer.set_learning_rate can reach the
            # live (device-side) opt_state even when the optimizer was
            # constructed without a model.
            optimizer.ffmodel = self
            # Commit the optimizer's scalars (step, lr) to the mesh like
            # the op-state above: the train step hands them back
            # mesh-placed, and an uncommitted first call compiles the
            # whole step a second time (seen on the chip: 46 s + 36 s).
            self.opt_state = jax.tree.map(
                lambda x: x if getattr(x, "ndim", 0) else jax.device_put(
                    x, self.policy.replicated()),
                optimizer.init_state(params))

        compute_dtype = jnp.dtype(self.config.compute_dtype)

        # per-layer weight regularizers (reference keras/regularizers.py):
        # the attr is always None or a non-empty list of ("l1"|"l2", coeff)
        # pairs (normalized + validated by _normalize_regularizer at build)
        reg_terms = []
        for layer in self.layers:
            for kind, coeff in layer.attrs.get("kernel_regularizer") or []:
                reg_terms.append((layer.name, "kernel", kind, coeff))

        def loss_and_out(p, feeds, label, rng, state):
            ctx = OpContext(training=True, rng=rng, compute_dtype=compute_dtype,
                            mesh=self.mesh, config=self.config)
            values, new_state = self._run_graph(p, feeds, ctx, state)
            out = values[self._final_tensor.tensor_id]
            logits = (values[self._logits_tensor.tensor_id]
                      if self._logits_tensor is not None else None)
            loss = compute_loss(self.loss_type, out, label, logits=logits)
            for lname, wname, kind, coeff in reg_terms:
                w = p[lname][wname]
                pen = (jnp.sum(jnp.abs(w)) if kind == "l1"
                       else jnp.sum(jnp.square(w)))
                loss = loss + coeff * pen
            return loss, (out, new_state)

        fwd = loss_and_out
        if self.config.remat:
            fwd = jax.checkpoint(loss_and_out, static_argnums=())

        # Where the policy (or the search) placed each weight, and the
        # optimizer state beside it, is a decision the step must hand back
        # unchanged: left free, GSPMD re-places the small vectors (biases,
        # norm scales) after the first step, the placement that runs is no
        # longer the one that was chosen, and the second step compiles the
        # whole program again for it.
        placed = (jax.tree.map(lambda x: x.sharding,
                               (params, self.opt_state))
                  if optimizer is not None else None)

        def train_step(p, opt_state, state, feeds, label, rng):
            (loss, (out, new_state)), grads = jax.value_and_grad(
                fwd, has_aux=True)(p, feeds, label, rng, state)
            new_p, new_opt = jax.lax.with_sharding_constraint(
                self.optimizer.update_step(p, grads, opt_state), placed)
            step_metrics = compute_step_metrics(self.metrics, out, label,
                                                self.loss_type)
            return new_p, new_opt, new_state, loss, step_metrics

        def eval_step(p, state, feeds, label):
            ctx = OpContext(training=False, rng=None,
                            compute_dtype=compute_dtype, mesh=self.mesh,
                            config=self.config)
            values, _ = self._run_graph(p, feeds, ctx, state)
            out = values[self._final_tensor.tensor_id]
            logits = (values[self._logits_tensor.tensor_id]
                      if self._logits_tensor is not None else None)
            loss = (compute_loss(self.loss_type, out, label, logits=logits)
                    if self.loss_type else jnp.zeros(()))
            step_metrics = compute_step_metrics(self.metrics, out, label,
                                                self.loss_type)
            return out, loss, step_metrics

        def predict_step(p, state, feeds):
            ctx = OpContext(training=False, rng=None,
                            compute_dtype=compute_dtype, mesh=self.mesh,
                            config=self.config)
            values, _ = self._run_graph(p, feeds, ctx, state)
            return values[self._final_tensor.tensor_id]

        def train_block(p, opt_state, state, feeds_stack, labels, rng):
            """K fused train steps — lax.scan over pre-staged batches.

            The training twin of the serving engines' fused blocks
            (serve/engine.py): one device call per K steps instead of one
            per step, amortizing the per-call dispatch/argument overhead
            of small steps (the reference amortizes with Legion's async
            future pipeline)."""

            def body(carry, xs):
                p, opt_state, state = carry
                feeds, label, step_rng = xs
                np_, no_, ns_, loss, met = train_step(
                    p, opt_state, state, feeds, label, step_rng)
                return (np_, no_, ns_), (loss, met)

            (p, opt_state, state), (losses, mets) = jax.lax.scan(
                body, (p, opt_state, state), (feeds_stack, labels, rng))
            return p, opt_state, state, losses, mets

        def train_block_unrolled(K):
            """Python-unrolled K-step block: same contract as train_block
            but with no scan region — XLA lowers convolutions markedly
            worse inside scan (measured ~17x on ResNet-50/v5e), so conv
            nets amortize per-call dispatch with an unrolled block
            instead. Compile time grows with K; keep K small (2-8)."""

            def block(p, opt_state, state, feeds_stack, labels, rng):
                losses, metlist = [], []
                for i in range(K):
                    feeds = {k: v[i] for k, v in feeds_stack.items()}
                    p, opt_state, state, loss, met = train_step(
                        p, opt_state, state, feeds, labels[i], rng[i])
                    losses.append(loss)
                    metlist.append(met)
                mets = {k: jnp.stack([m[k] for m in metlist])
                        for k in metlist[0]}
                return p, opt_state, state, jnp.stack(losses), mets

            return jax.jit(block, donate_argnums=(0, 1, 2))

        if optimizer is not None:
            self._train_step = jax.jit(train_step, donate_argnums=(0, 1, 2))
            self._train_block = jax.jit(train_block,
                                        donate_argnums=(0, 1, 2))
            self._unrolled_blocks = {}

            def _get_unrolled(K):
                if K not in self._unrolled_blocks:
                    self._unrolled_blocks[K] = train_block_unrolled(K)
                return self._unrolled_blocks[K]

            self._train_block_unrolled = _get_unrolled
        self._eval_step = jax.jit(eval_step)
        self._predict_step = jax.jit(predict_step)
        self._compiled = True

    def _note_block_diffusion(self, split):
        """``self.block_diffusion``: the serve/batch_config.BlockDiffusion
        a model's head carries (``unmasking_head``), None for every other
        model: what the decode block, the scheduler and the telemetry read
        off the compiled model to know that a step fills a block and yields
        a count of tokens. Refuses here what cannot serve such a model."""
        head = self.layers[-1].attrs if self.layers else {}
        blocks = {ly.attrs["block_length"] for ly in self.layers
                  if "block_length" in ly.attrs}
        bd = self.block_diffusion = head.get("diffusion")
        if bd is None:
            if blocks:
                raise NotImplementedError(
                    "attention layers with a block_length need the "
                    "unmasking_head that fills their blocks")
            return
        from flexflow_tpu.ops.inc_attention import refuse_block_diffusion
        from flexflow_tpu.serve.request_manager import RequestManager

        cfg, B = self.config, bd.block_length
        if blocks != {B}:
            raise NotImplementedError(
                f"a head that fills blocks of {B} over attention layers "
                f"that see blocks of {sorted(blocks)}")
        if split:
            refuse_block_diffusion(self,
                                   f"a mesh that divides a model ({split})")
        if cfg.inference_debugging:
            refuse_block_diffusion(
                self, "inference_debugging (its dumped decode runs through "
                "InferenceManager.step, one token a row)")
        if cfg.decode_width not in (0, B):
            raise NotImplementedError(
                f"FFConfig.decode_width {cfg.decode_width} over a model "
                f"whose decode step is its block of {B}")
        chunk, _ = RequestManager._prefill_shape(cfg)
        if cfg.max_sequence_length % B or chunk % B:
            raise NotImplementedError(
                f"block-diffusion serving keeps whole blocks of {B}: "
                f"max_sequence_length {cfg.max_sequence_length} and the "
                f"prefill chunk {chunk} (max_tokens_per_batch over at most "
                "four rows) have to be multiples of it")

    def _consolidate_kv_caches(self):
        """Stack homogeneous per-layer KV caches into two [L, ...] arrays.

        Cuts the per-call donated-buffer count from 2*num_layers to 2 and
        lets the speculative tree commit vectorize over layers. Layers get
        attrs["cache_layer_idx"]; see ops/inc_attention.py read_kv/write_kv.
        A model with windowed layers has one pair of stacks a kind: the
        full caches as ever, the rings under inc_attention.WINDOW_STACK
        (those layers carry attrs["cache_stack"] too). Latent layers
        (ops/latent_attention.py) keep one stream each: their stack, of any
        depth, is inc_attention.LATENT_STACK.
        """
        from flexflow_tpu.ops import recurrent as REC
        from flexflow_tpu.ops.inc_attention import (CHUNKED_STACK,
                                                    FULL_STACK, LATENT_STACK,
                                                    RECURRENT_STACK,
                                                    TAIL_STACK, WINDOW_STACK)

        by_name = {layer.name: layer for layer in self.layers}
        recurrent = [n for n, st in self.op_state.items()
                     if isinstance(st, dict) and REC.STATE in st]
        if recurrent:
            # layers that keep a recurrent state and no cache of positions
            # (the contract of ops/recurrent.py, whatever the op): the
            # states and the convolutions' tails are one stack each at any
            # depth, beside whatever the model's other attention layers
            # keep (plain k/v caches, below)
            stack = {}
            for member, key in (("s", REC.STATE), ("u", REC.TAIL)):
                shapes = {(self.op_state[n][key].shape,
                           self.op_state[n][key].dtype) for n in recurrent}
                if len(shapes) != 1:
                    raise NotImplementedError(
                        "layers that keep recurrent states of different "
                        f"shapes in one model ({sorted(map(str, shapes))}): "
                        "they are served from one stack, a layer an index")
                ((shape, dtype),) = shapes
                stack[member] = jnp.zeros((len(recurrent),) + shape, dtype)
            for i, n in enumerate(recurrent):
                by_name[n].attrs["state_layer_idx"] = i
                del self.op_state[n]
            self.op_state[RECURRENT_STACK] = stack
            plain = [n for n, st in self.op_state.items()
                     if isinstance(st, dict) and "k_cache" in st]
            if any(by_name[n].attrs.get(k) is not None for n in plain
                   for k in ("sliding_window", "eva_window")):
                raise NotImplementedError(
                    "layers that keep a recurrent state beside windowed or "
                    "chunked attention layers in one model")
            first = by_name[recurrent[0]]
            # what telemetry says of the two kinds (ffsv_kv_cache_bytes,
            # ffsv_attn_positions_read_total{kind="full"}, and the three
            # ffsv_kda_* series, which count this kind whatever the op),
            # and what says "this model keeps a recurrent state"
            self.attention_kinds = {
                "full": {"layers": len(plain), "window": None,
                         "cache_bytes": sum(
                             2 * self.op_state[n]["k_cache"].nbytes
                             for n in plain)},
                "recurrent": {"layers": len(recurrent), "window": None,
                              "cache_bytes": sum(a.nbytes
                                                 for a in stack.values()),
                              "state_bytes": stack["s"].nbytes,
                              "conv_bytes": stack["u"].nbytes,
                              "op": first.op_type.name,
                              # a prefill step's chunked form is a kernel
                              # (ffsv_kda_chunk_tokens_total counts then)
                              "chunk_kernel": get_op_impl(
                                  first.op_type).takes_chunk_kernel(
                                      first.attrs, self.config)}}
        tails = [n for n, st in self.op_state.items()
                 if isinstance(st, dict) and "tail" in st]
        if tails:
            # layers that carry a row's tail beside a plain k/v cache
            # (ops/cca_attention.py): the tails are one stack at any depth,
            # the caches go the way of every plain cache below
            (shape,) = {self.op_state[n]["tail"].shape for n in tails}
            (dtype,) = {self.op_state[n]["tail"].dtype for n in tails}
            for i, n in enumerate(tails):
                by_name[n].attrs["tail_layer_idx"] = i
                del self.op_state[n]["tail"]
            self.op_state[TAIL_STACK] = {
                "t": jnp.zeros((len(tails),) + shape, dtype)}
            # what telemetry says of the kind, and what says "this model
            # carries a tail" (ffsv_kv_cache_bytes{kind="full"|"tail"},
            # ffsv_attn_positions_read_total{kind="full"},
            # ffsv_cca_tails_total)
            self.attention_kinds = {"full": {
                "layers": len(tails), "window": None,
                "cache_bytes": sum(2 * self.op_state[n]["k_cache"].nbytes
                                   for n in tails),
                "tail_bytes": self.op_state[TAIL_STACK]["t"].nbytes}}
        latent = [n for n, st in self.op_state.items()
                  if isinstance(st, dict) and "c_cache" in st]
        if latent:
            (shape,) = {self.op_state[n]["c_cache"].shape for n in latent}
            (dtype,) = {self.op_state[n]["c_cache"].dtype for n in latent}
            for i, n in enumerate(latent):
                by_name[n].attrs["cache_layer_idx"] = i
                del self.op_state[n]
            self.op_state[LATENT_STACK] = {
                "c": jnp.zeros((len(latent),) + shape, dtype)}
            # what telemetry says of the kind (ffsv_kv_cache_bytes,
            # ffsv_attn_positions_read_total)
            self.attention_kinds = {"latent": {
                "layers": len(latent), "window": None,
                "cache_bytes": self.op_state[LATENT_STACK]["c"].nbytes}}
        names = [n for n, st in self.op_state.items()
                 if isinstance(st, dict) and "k_cache" in st]
        if latent and names:
            raise NotImplementedError(
                "latent attention layers beside k/v ones in one model")
        if tails and len(tails) != len(names):
            raise NotImplementedError(
                "attention layers that carry a tail beside others in one "
                "model")
        rings = [n for n in names
                 if by_name[n].attrs.get("sliding_window") is not None]
        chunked = [n for n in names
                   if by_name[n].attrs.get("eva_window") is not None]
        if chunked:
            # one stream of two extents a layer, its own stack at any depth
            if len(chunked) != len(names):
                raise NotImplementedError(
                    "chunked attention layers beside others in one model")
            kinds = {CHUNKED_STACK: chunked}
            (window,) = {by_name[n].attrs["eva_window"] for n in chunked}
            (chunk,) = {by_name[n].attrs["chunk_size"] for n in chunked}
            # what telemetry says of the kind (ffsv_kv_cache_bytes; the
            # positions read are counted by extent, note_attention_reads)
            self.attention_kinds = {"chunked": {
                "layers": len(chunked), "window": window, "chunk": chunk,
                "cache_bytes": sum(2 * self.op_state[n]["k_cache"].nbytes
                                   for n in chunked)}}
        elif rings:
            kinds = {FULL_STACK: [n for n in names if n not in rings],
                     WINDOW_STACK: rings}
        elif len(names) < 2 and not (names and (
                recurrent or self.loop_region is not None)):
            return
        else:               # (beside recurrent layers even one is a stack)
            kinds = {FULL_STACK: names}
        if rings:
            # what telemetry says of the two kinds (ffsv_kv_cache_bytes,
            # ffsv_attn_positions_read_total): only such a model has it
            (window,) = {by_name[n].attrs["sliding_window"] for n in rings}
            self.attention_kinds = {
                kind: {"layers": len(kinds[key]), "window": w,
                       "cache_bytes": sum(
                           2 * self.op_state[n]["k_cache"].nbytes
                           for n in kinds[key])}
                for kind, key, w in (("full", FULL_STACK, None),
                                     ("window", WINDOW_STACK, window))}
        for key, names in kinds.items():    # (one kind unless ``rings``)
            if not names:           # every layer of a model may be windowed
                continue
            shapes = {self.op_state[n]["k_cache"].shape for n in names}
            dtypes = {self.op_state[n]["k_cache"].dtype for n in names}
            if len(shapes) != 1 or len(dtypes) != 1:
                if rings:
                    raise NotImplementedError(
                        "windowed layers beside full ones need one cache "
                        f"shape a kind; {key} has {sorted(shapes)}")
                if self.loop_region is not None:
                    raise NotImplementedError(
                        "a loop region's attention layers keep their planes "
                        f"in one stack: one cache shape, not {sorted(shapes)}")
                return  # heterogeneous caches keep the per-layer layout
            planes = self._number_planes(names, by_name)
            for n in names:
                if key != FULL_STACK:
                    by_name[n].attrs["cache_stack"] = key
            # A cache starts zeroed, so allocate the stacks instead of
            # copying the per-layer buffers into them, and free those
            # first: a jnp.stack holds both generations at once, which a
            # cache sized to fill the chip beside the weights cannot afford
            # (seen on the chip: 7B int8 + 8 x 1024 slots, 2 GiB short of
            # 16).
            for n in names:
                del self.op_state[n]
            shape, dtype = (planes,) + shapes.pop(), dtypes.pop()
            self.op_state[key] = {"k": jnp.zeros(shape, dtype),
                                  "v": jnp.zeros(shape, dtype)}
            if self.loop_region is not None:
                # what telemetry says of the planes (ffsv_kv_cache_bytes,
                # ffsv_attn_positions_read_total{kind="full"}): a decode
                # step reads every pass's plane of every live position
                self.attention_kinds = {"full": {
                    "layers": planes, "window": None,
                    "cache_bytes": 2 * self.op_state[key]["k"].nbytes}}

    def _number_planes(self, names, by_name) -> int:
        """Give the layers ``names`` of one stack their planes, in order:
        ``attrs["cache_layer_idx"]``, one a layer; a loop region's span
        takes ``steps`` planes a layer, pass ``t`` of its layer ``l`` at
        ``cache_layer_idx + t * attrs["loop_planes"]``
        (inc_attention.cache_plane). Returns the planes in all."""
        region = self.loop_region
        looped = ([ly.name for ly in region.cache_layers]
                  if region is not None else [])
        at = 0
        for n in names:
            attrs = by_name[n].attrs
            attrs.pop("loop_planes", None)
            if n in looped:
                attrs["cache_layer_idx"] = at + looped.index(n)
                attrs["loop_planes"] = len(looped)
                if n == looped[-1]:
                    at += region.steps * len(looped)
            else:
                attrs["cache_layer_idx"] = at
                at += 1
        return at

    # ==================================================================
    # Training verbs (reference model.cc:2784/2807/2838 + fit)
    # ==================================================================
    def batch_sharding(self, shape):
        if self.policy is None:
            return None
        return self.policy.batch_sharding(tuple(shape))

    def _feeds_from_arrays(self, xs: List[np.ndarray]) -> Dict[int, Any]:
        assert len(xs) == len(self.input_tensors), (
            f"model has {len(self.input_tensors)} inputs, got {len(xs)}")
        feeds = {}
        for t, x in zip(self.input_tensors, xs):
            arr = jnp.asarray(x, dtype=t.dtype.to_jnp())
            if self.policy is not None:
                arr = jax.device_put(arr, self.policy.batch_sharding(arr.shape))
            feeds[t.tensor_id] = arr
        return feeds

    def train_one_batch(self, xs: List[np.ndarray], y: np.ndarray):
        assert self._compiled and self.optimizer is not None
        self._rng, step_rng = jax.random.split(self._rng)
        feeds = self._feeds_from_arrays(xs)
        label = jnp.asarray(y, dtype=self.label_tensor.dtype.to_jnp())
        if self.policy is not None:
            label = jax.device_put(label, self.policy.batch_sharding(label.shape))
        import time as _time

        t0 = _time.perf_counter() if self.config.profiling else 0.0
        (self.params, self.opt_state, self.op_state, loss,
         step_metrics) = self._train_step(self.params, self.opt_state,
                                          self.op_state, feeds, label, step_rng)
        if self.config.profiling:
            # --profiling parity: per-step timing, fenced by host
            # readback (utils/profiling.device_fence)
            from flexflow_tpu.utils.profiling import device_fence

            device_fence(loss)
            self._step_timer.record("train_step",
                                    _time.perf_counter() - t0)
        bs = y.shape[0]
        self._perf.update({k: float(v) for k, v in step_metrics.items()}, bs)
        return float(loss)

    def train_batches(self, xs: List[np.ndarray], y: np.ndarray,
                      unroll: bool = False):
        """Run K train steps in ONE device call (lax.scan block).

        ``xs``: per-input arrays stacked [K, batch, ...]; ``y``:
        [K, batch, 1]. Returns the K per-step losses. Metrics accumulate
        exactly as K train_one_batch calls would. Use when per-step
        dispatch overhead matters (small fast steps) and
        the next K batches can be staged up front — fit(steps_per_call=K)
        does the batching for you. Caveat: XLA lowers CONVOLUTIONS
        markedly worse inside the scan region (measured ~17x slower on
        ResNet-50 on v5e) — pass ``unroll=True`` for conv graphs to use a
        python-unrolled block (no scan region, per-K compile cache).
        """
        assert self._compiled and self.optimizer is not None
        K = y.shape[0]
        # replicate the SEQUENTIAL rng stream exactly (one split per step,
        # same post-state), so K blocked steps == K train_one_batch calls
        # bit-for-bit even for stochastic graphs (dropout)
        step_rngs = []
        for _ in range(K):
            self._rng, r = jax.random.split(self._rng)
            step_rngs.append(r)
        block_rngs = jnp.stack(step_rngs)

        def put_stacked(arr):
            # batch sharding applies per STEP: dim 0 is the scan (step)
            # axis, the data axis shards dim 1
            if self.policy is None:
                return arr
            from jax.sharding import NamedSharding, PartitionSpec

            inner = self.policy.batch_sharding(arr.shape[1:])
            return jax.device_put(arr, NamedSharding(
                inner.mesh, PartitionSpec(None, *inner.spec)))

        assert len(xs) == len(self.input_tensors), (
            f"model has {len(self.input_tensors)} inputs, got {len(xs)}")
        feeds_stack = {
            t.tensor_id: put_stacked(jnp.asarray(a, dtype=t.dtype.to_jnp()))
            for t, a in zip(self.input_tensors, xs)}
        labels = jnp.asarray(y, dtype=self.label_tensor.dtype.to_jnp())
        labels = put_stacked(labels)
        import time as _time

        t0 = _time.perf_counter() if self.config.profiling else 0.0
        block_fn = (self._train_block_unrolled(K) if unroll
                    else self._train_block)
        (self.params, self.opt_state, self.op_state, losses,
         mets) = block_fn(self.params, self.opt_state,
                          self.op_state, feeds_stack, labels,
                          block_rngs)
        losses = np.asarray(losses)              # fences the block
        if self.config.profiling:
            # --profiling parity with train_one_batch: per-step timing
            # (amortized over the fused block)
            dt = (_time.perf_counter() - t0) / K
            for _ in range(K):
                self._step_timer.record("train_step", dt)
        bs = y.shape[1]
        mets = {k: np.asarray(v) for k, v in mets.items()}
        for i in range(K):
            self._perf.update({k: float(v[i]) for k, v in mets.items()}, bs)
        return [float(l) for l in losses]

    def fit(self, x=None, y=None, batch_size: Optional[int] = None,
            epochs: Optional[int] = None, shuffle: bool = False,
            initial_epoch: int = 0, steps_per_call: int = 1,
            unroll: bool = False):
        """Keras-style fit (reference flexflow_cffi.py:3534).

        ``initial_epoch`` offsets the shuffle seed so outer epoch loops
        (e.g. the Keras frontend calling fit(epochs=1) per epoch for
        callbacks) still get a fresh permutation each epoch."""
        xs = x if isinstance(x, (list, tuple)) else [x]
        xs = [np.asarray(a) for a in xs]
        y = np.asarray(y)
        bs = batch_size or self.config.batch_size
        epochs = epochs or self.config.epochs
        if y.shape[0] < bs:
            raise ValueError(
                f"fit() needs at least one full batch: {y.shape[0]} samples "
                f"< batch_size {bs}")
        history = []
        for epoch in range(epochs):
            self.reset_metrics()
            losses = []
            pend: List[Any] = []
            for batch in minibatches(list(xs) + [y], bs, shuffle=shuffle,
                                     seed=self.config.seed + initial_epoch
                                     + epoch):
                *bxs, by = batch
                if steps_per_call <= 1:
                    losses.append(self.train_one_batch(bxs, by))
                    continue
                pend.append((bxs, by))
                if len(pend) == steps_per_call:
                    losses.extend(self.train_batches(
                        [np.stack(a) for a in zip(*(p[0] for p in pend))],
                        np.stack([p[1] for p in pend]), unroll=unroll))
                    pend = []
            for bxs, by in pend:        # epoch tail < steps_per_call
                losses.append(self.train_one_batch(bxs, by))
            history.append({"epoch": epoch, "loss": float(np.mean(losses)),
                            **self._metrics_summary()})
            print(f"epoch {epoch}: loss={history[-1]['loss']:.4f} "
                  f"{self._perf.report()}"
                  + (f" [{self._step_timer.report()}]"
                     if self.config.profiling else ""))
        return history

    def evaluate(self, x=None, y=None, batch_size: Optional[int] = None):
        xs = x if isinstance(x, (list, tuple)) else [x]
        xs = [np.asarray(a) for a in xs]
        y = np.asarray(y)
        bs = batch_size or self.config.batch_size
        if y.shape[0] < bs:
            raise ValueError(
                f"evaluate() needs at least one full batch: {y.shape[0]} "
                f"samples < batch_size {bs}")
        self.reset_metrics()
        losses = []
        for batch in minibatches(list(xs) + [y], bs):
            *bxs, by = batch
            feeds = self._feeds_from_arrays(bxs)
            label = jnp.asarray(by, dtype=self.label_tensor.dtype.to_jnp())
            _, loss, step_metrics = self._eval_step(self.params, self.op_state,
                                                    feeds, label)
            losses.append(float(loss))
            self._perf.update({k: float(v) for k, v in step_metrics.items()},
                              by.shape[0])
        return {"loss": float(np.mean(losses)), **self._metrics_summary()}

    def predict(self, x) -> np.ndarray:
        if not self._compiled:
            raise RuntimeError("FFModel.compile() must be called before "
                               "predict/fit/evaluate")
        xs = x if isinstance(x, (list, tuple)) else [x]
        feeds = self._feeds_from_arrays([np.asarray(a) for a in xs])
        return np.asarray(self._predict_step(self.params, self.op_state, feeds))

    # manual-loop parity verbs -----------------------------------------
    def forward(self, xs: Optional[List[np.ndarray]] = None,
                seq_length: Optional[int] = None):
        if xs is not None:
            self._pending_batch = [np.asarray(a) for a in xs]

    def backward(self, seq_length: Optional[int] = None):
        pass  # fused into update() — XLA computes fwd+bwd in one program

    def update(self, y: Optional[np.ndarray] = None):
        if y is not None and self._pending_batch is None:
            raise ValueError("update(y) needs a prior forward(xs) call to "
                             "stage the input batch")
        if y is None:
            raise ValueError(
                "flexflow_tpu fuses forward/backward/update into one jitted "
                "step: call train_one_batch(xs, y) (or fit) instead of the "
                "three-verb loop, or pass the label to update(y).")
        return self.train_one_batch(self._pending_batch, y)

    def zero_gradients(self):
        pass  # gradients are recomputed functionally each step

    def reset_metrics(self):
        self._perf = PerfMetrics()

    def _metrics_summary(self):
        out = {}
        if MetricsType.METRICS_ACCURACY in self.metrics:
            out["accuracy"] = self._perf.accuracy
        return out

    @property
    def perf_metrics(self) -> PerfMetrics:
        return self._perf

    # ==================================================================
    # Parameter access (reference Tensor.get/set_weights via inline mapping)
    # ==================================================================
    def get_parameter_tensor(self, layer_name: str, weight_name: str) -> Tensor:
        for layer in self.layers:
            if layer.name == layer_name:
                for w in layer.weights:
                    if w.name == weight_name:
                        return Tensor(w.shape, w.dtype, name=f"{layer_name}.{weight_name}",
                                      model=self, is_weight=True,
                                      weight_name=(layer_name, weight_name))
        raise KeyError((layer_name, weight_name))

    def finalize_pipeline(self):
        """Stack block weights onto the pipe axis (no-op without a plan).
        Call after loading weights; LLM.compile does this automatically."""
        if self._pp_plan is not None:
            from flexflow_tpu.serve.pipeline_plan import finalize_pipeline

            finalize_pipeline(self)
        return self

    def get_parameter_by_key(self, key: Tuple[str, str]) -> np.ndarray:
        layer_name, weight_name = key
        from flexflow_tpu.quant import dequantize_array, is_quantized

        if layer_name not in self.params:
            from flexflow_tpu.serve.pipeline_plan import (PP_PARAMS_KEY,
                                                          stacked_param_lookup)

            hit = stacked_param_lookup(self, layer_name, weight_name)
            if hit is not None:
                pos, i = hit
                stack = self.params[PP_PARAMS_KEY][pos][weight_name]
                if is_quantized(stack):
                    from flexflow_tpu.quant import QuantizedWeight

                    layer_qw = QuantizedWeight(stack.qtype, stack.q[i],
                                               stack.scale[i], stack.rows,
                                               stack.dtype)
                    return np.asarray(dequantize_array(layer_qw))
                return np.asarray(stack[i])
        leaf = self.params[layer_name][weight_name]
        if is_quantized(leaf):
            return np.asarray(dequantize_array(leaf))
        return np.asarray(leaf)

    def offload_weights(self, min_bytes: int = 1 << 20) -> int:
        """Page big weights to pinned host memory; the jitted step streams
        them back per layer (reference -offload mode, config.h:144;
        compute path in flexflow_tpu/offload.py). Returns bytes moved."""
        from flexflow_tpu.offload import offload_model_weights

        moved = offload_model_weights(self, min_bytes=min_bytes)
        if self.config.profiling:
            print(f"offload_weights: {moved / 1e6:.1f}MB -> pinned_host")
        return moved

    def quantize_weights(self, qtype: str):
        """Compress eligible weights to int8/int4 on device (reference
        4/8-bit weight quantization, config.h:161-163; compute path in
        flexflow_tpu/quant.py). Inference-only: quantized params are not
        trainable."""
        from flexflow_tpu.quant import quantize_params, quantized_nbytes

        if self.optimizer is not None:
            raise RuntimeError(
                "quantize_weights is inference-only: int8/int4 params are "
                "not differentiable — compile without an optimizer")
        before = quantized_nbytes(self.params)
        self.params = quantize_params(self.params, qtype)
        after = quantized_nbytes(self.params)
        if self.config.profiling:
            print(f"quantize_weights({qtype}): {before / 1e6:.1f}MB -> "
                  f"{after / 1e6:.1f}MB")
        return self

    def set_parameter_by_key(self, key: Tuple[str, str], value: np.ndarray):
        layer_name, weight_name = key
        from flexflow_tpu.quant import is_quantized, quantize_array

        if layer_name not in self.params:
            from flexflow_tpu.serve.pipeline_plan import (PP_PARAMS_KEY,
                                                          stacked_param_lookup)

            hit = stacked_param_lookup(self, layer_name, weight_name)
            if hit is not None:
                pos, i = hit
                stack = self.params[PP_PARAMS_KEY][pos][weight_name]
                if is_quantized(stack):
                    # re-quantize the block's new weights and splice the
                    # payload+scale into the stage-stacked leaves
                    arr = jnp.asarray(value, dtype=jnp.dtype(stack.dtype))
                    # logical per-block shape (int4 packs two rows/byte)
                    assert arr.shape == (stack.rows, stack.q.shape[-1]), (
                        arr.shape, stack.rows, stack.q.shape)
                    new = quantize_array(arr, stack.qtype)
                    stack.q = stack.q.at[i].set(new.q)
                    stack.scale = stack.scale.at[i].set(new.scale)
                    return
                arr = jnp.asarray(value, dtype=stack.dtype)
                assert arr.shape == stack.shape[1:], (arr.shape, stack.shape)
                self.params[PP_PARAMS_KEY][pos][weight_name] = \
                    stack.at[i].set(arr)
                return
        old = self.params[layer_name][weight_name]
        if is_quantized(old):   # writes to a quantized weight re-quantize
            arr = jnp.asarray(value, dtype=jnp.dtype(old.dtype))
            assert arr.shape == old.shape, (arr.shape, old.shape)
            new = quantize_array(arr, old.qtype)
            # keep the load-time shardings of the payload/scale
            new.q = jax.device_put(new.q, old.q.sharding)
            new.scale = jax.device_put(new.scale, old.scale.sharding)
            self.params[layer_name][weight_name] = new
            return
        arr = jnp.asarray(value, dtype=old.dtype)
        assert arr.shape == old.shape, (arr.shape, old.shape)
        self.params[layer_name][weight_name] = jax.device_put(arr, old.sharding)

    def _estimate_layer_costs(self) -> Dict[str, float]:
        """Per-layer forward-time estimates from the search cost model
        (feeds --include-costs-dot-graph; reference attaches simulator costs
        to the exported graph)."""
        from flexflow_tpu.search.cost_model import CostModel
        from flexflow_tpu.search.machine_model import MachineModel
        from flexflow_tpu.search.pcg import PCG
        from flexflow_tpu.search.strategy import OpStrategy, replicated

        pcg = PCG.from_model(self)
        machine = MachineModel.from_name(
            self.config.tpu_chip, self.config.resolve_num_devices())
        axis_degrees = (dict(self.mesh.shape)
                        if getattr(self, "mesh", None) is not None else {})
        cm = CostModel(machine, axis_degrees=axis_degrees, training=False)
        costs: Dict[str, float] = {}
        for node in pcg.nodes:
            st = None
            if self.strategy is not None:
                st = self.strategy.ops.get(node.name)
            if st is None:
                out_nd = len(node.output_shapes[0]) if node.output_shapes \
                    else 1
                st = OpStrategy(
                    input_specs=tuple(replicated(len(s))
                                      for s in node.input_shapes),
                    output_spec=replicated(out_nd))
            costs[node.name] = cm.node_compute_time(node, st).forward_time
        return costs

    def export_dot(self, path: str, include_costs: bool = False,
                   costs=None) -> str:
        """Graphviz export of the computation graph (reference
        export_strategy_computation_graph_file)."""
        from flexflow_tpu.utils.dot import export_model_dot

        return export_model_dot(self, path, include_costs=include_costs,
                                costs=costs, strategy=self.strategy)

    def recompile_on_condition(self, recompile_state) -> bool:
        """Dynamic recompilation hook (reference model.cc:2791)."""
        from flexflow_tpu.core.recompile import recompile_on_condition

        return recompile_on_condition(self, recompile_state)

    def get_layers(self) -> Dict[int, Layer]:
        return dict(enumerate(self.layers))

    def get_output_tensor(self) -> Tensor:
        return self._final_tensor
