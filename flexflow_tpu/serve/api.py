"""User-facing serving API: ``LLM``, ``SSM``, ``init``.

Capability parity with the reference Python serve API (reference
python/flexflow/serve/serve.py: LLM :71 with .compile :305 / .generate :407,
SSM :429, and serve/__init__.py init() :94): an LLM wraps a HuggingFace
checkpoint, compiles it into a serving FFModel (incremental decoding, or
tree-verify when draft SSMs are attached), and generates through the
RequestManager's continuous-batching loops.

TPU-first: no weight-file export/reload round trip (the reference converts
HF checkpoints to a binary per-layer layout, serve.py:167-303, then
file_loader.cc re-reads them) — the HF state dict maps straight into the
sharded param pytree, and TP/PP degrees become mesh axes instead of
MachineView assignments.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from flexflow_tpu.config import FFConfig
from flexflow_tpu.ffconst import CompMode, DataType, InferenceMode
from flexflow_tpu.serve.batch_config import GenerationConfig
from flexflow_tpu.serve.request_manager import (GenerationResult,
                                                RequestManager)
from flexflow_tpu.utils.deep_stack import with_deep_stack

_global_init_kwargs: dict = {}


def init(configs_dict: Optional[dict] = None, **kwargs):
    """Configure serving defaults (reference serve/__init__.py init() :94).

    The reference synthesizes Legion argv (num_gpus, memory_per_gpu,
    zero_copy_memory_per_node, ...). On TPU there is no resource argv to
    build — accepted keys that map to FFConfig fields are stored and applied
    to every subsequently-created LLM; Legion-only keys are ignored.
    """
    global _global_init_kwargs
    merged = dict(configs_dict or {})
    merged.update(kwargs)
    known = {f.name for f in FFConfig.__dataclass_fields__.values()}
    aliases = {
        "num_gpus": "num_devices",
        "num_cpus": None,
        "memory_per_gpu": None,
        "zero_copy_memory_per_node": None,
        "legion_utility_processors": None,
        "use_4bit_quantization": ("quantization_type", "int4"),
        "use_8bit_quantization": ("quantization_type", "int8"),
        "offload": ("cpu_offload", True),
        "fusion": "enable_fusion",
    }
    out = {}
    for k, v in merged.items():
        if k in known:
            out[k] = v
        elif k in aliases:
            a = aliases[k]
            if a is None:
                continue  # Legion resource knob with no TPU meaning
            if isinstance(a, tuple):
                if v:
                    out[a[0]] = a[1]
            else:
                out[a] = v
        # unknown keys ignored (parse_known_args parity)
    _global_init_kwargs = out
    return out


def _is_hf_model(obj) -> bool:
    return hasattr(obj, "state_dict") and hasattr(obj, "config")


class LLM:
    """A large language model to serve (reference serve/serve.py:71).

    ``model`` may be:
      * a transformers ``PreTrainedModel`` (weights already in memory),
      * a local HF checkpoint directory (loaded via transformers),
      * a ``(hf_config, state_dict)`` pair.
    """

    inference_mode = InferenceMode.INC_DECODING_MODE

    def __init__(self, model: Any,
                 data_type: DataType = DataType.DT_FLOAT,
                 tokenizer: Any = None,
                 cache_path: str = "",
                 refresh_cache: bool = False,
                 output_file: str = ""):
        from flexflow_tpu.models import family_for_hf_config

        self.data_type = data_type
        self.output_file = output_file
        self.tokenizer = tokenizer
        self.ffmodel = None
        self.ssms: List["SSM"] = []
        self.rm: Optional[RequestManager] = None
        self._server: Optional[_BackgroundServer] = None

        if isinstance(model, (tuple, list)) and len(model) == 2:
            self.hf_config, self._state_dict = model
        elif _is_hf_model(model):
            self.hf_config = model.config
            self._state_dict = model.state_dict()
        elif isinstance(model, str):
            import transformers

            local = os.path.isdir(model)
            hf = transformers.AutoModelForCausalLM.from_pretrained(
                model, local_files_only=local)
            self.hf_config = hf.config
            self._state_dict = hf.state_dict()
            if self.tokenizer is None:
                sp_path = os.path.join(model, "tokenizer.model")
                if local and os.path.exists(sp_path):
                    # LLaMA-family SentencePiece model: the native tokenizer
                    # (native/src/sp_tokenizer.cpp) keeps transformers off
                    # the tokenize path entirely (reference: tokenizers-cpp
                    # selected by ModelType, request_manager.cc:109)
                    try:
                        from flexflow_tpu.native.sp_tokenizer import \
                            SentencePieceTokenizer

                        self.tokenizer = SentencePieceTokenizer(sp_path)
                    except Exception:
                        self.tokenizer = None   # corrupt model file: raw
                        # token-id prompts still work (pre-existing contract)
                if self.tokenizer is None:
                    try:
                        self.tokenizer = \
                            transformers.AutoTokenizer.from_pretrained(
                                model, local_files_only=local)
                    except Exception:
                        self.tokenizer = None
        else:
            raise TypeError(f"unsupported model source: {type(model)}")
        self.family = family_for_hf_config(self.hf_config)
        self.model_config = self.family.config_cls.from_hf_config(
            self.hf_config)

    # ------------------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, checkpoint_dir: str,
                        quantize: Optional[str] = None, **kwargs) -> "LLM":
        """Cold-start from an on-disk HF-layout checkpoint
        (``models/checkpoint_store.py``: config.json +
        model.safetensors / pytorch_model.bin).

        This is the disk-to-serving path replica respawn and autoscaling
        pay for (serve/replica.py measures it as ``cold_start_s``):
        read config -> build the family graph -> load the name-mapped
        weights at ``compile()`` -> optionally quantize on load
        (``quantize="int8"|"int4"``, applied right after the weights
        land so the fp copy never lingers). Token-identical to the
        in-memory build the checkpoint was saved from."""
        from flexflow_tpu.models.checkpoint_store import load_checkpoint
        from flexflow_tpu.quant import normalize_qtype

        cfg_dict, state_dict = load_checkpoint(checkpoint_dir)
        llm = cls((cfg_dict, state_dict), **kwargs)
        llm.checkpoint_dir = checkpoint_dir
        llm._quantize_on_load = normalize_qtype(quantize)
        return llm

    # ------------------------------------------------------------------
    def compile(self,
                generation_config: Optional[GenerationConfig] = None,
                max_requests_per_batch: int = 1,
                max_seq_length: int = 256,
                max_tokens_per_batch: int = 64,
                model_specific_data_parallelism_degree: int = 1,
                model_specific_tensor_parallelism_degree: int = 1,
                model_specific_pipeline_parallelism_degree: int = 1,
                ssms: Sequence["SSM"] = (),
                **ffconfig_kwargs):
        """Build + jit the serving graph (reference LLM.compile :305)."""
        self.generation_config = generation_config or GenerationConfig()
        self.ssms = list(ssms)
        mode = (InferenceMode.TREE_VERIFY_MODE if self.ssms
                else self.inference_mode)

        kw = dict(_global_init_kwargs)
        kw.update(ffconfig_kwargs)
        kw.setdefault("data_parallelism_degree",
                      model_specific_data_parallelism_degree)
        kw.setdefault("tensor_parallelism_degree",
                      model_specific_tensor_parallelism_degree)
        kw.setdefault("pipeline_parallelism_degree",
                      model_specific_pipeline_parallelism_degree)
        config = FFConfig(max_requests_per_batch=max_requests_per_batch,
                          max_sequence_length=max_seq_length,
                          max_tokens_per_batch=max_tokens_per_batch, **kw)
        if config.telemetry:
            # enable-or-keep the process-global telemetry (an enabled
            # instance's registry survives; SSM.compile reuses the
            # verifier's kwargs so this runs once per model) and attach
            # the requested trace path to the live tracer
            from flexflow_tpu.telemetry import ensure_telemetry

            ensure_telemetry(config.telemetry_trace_path or None)

        from flexflow_tpu.core.model import FFModel

        self.ffmodel = FFModel(config)
        self.family.build(self.ffmodel, self.model_config, mode=mode,
                          generation_config=self.generation_config,
                          data_type=self.data_type)
        self.ffmodel.compile(comp_mode=CompMode.COMP_MODE_INFERENCE)
        self.family.load_hf(self.ffmodel, self.model_config,
                            self._state_dict)
        # weights now live on device with their shardings; drop the host
        # copy so a 7B checkpoint doesn't stay resident twice
        self._state_dict = None
        if config.quantization_type:
            # 4/8-bit weight-only compression (reference --4bit/--8bit-
            # quantization flags): done post-load so scales see real weights
            self.ffmodel.quantize_weights(config.quantization_type)
        elif getattr(self, "_quantize_on_load", None):
            # from_checkpoint(quantize=...): same post-load compression,
            # requested at the checkpoint door instead of FFConfig
            self.ffmodel.quantize_weights(self._quantize_on_load)
        # stage-shard the transformer blocks over the "pipe" axis now that
        # weights are loaded (reference inference_manager.cc:91-132
        # places layer blocks per stage at model-compile time). Runs
        # BEFORE offload so paging applies to the stage-stacked leaves
        # (PP x offload composes, reference config.h:144-146)
        self.ffmodel.finalize_pipeline()
        if config.cpu_offload:
            # page (possibly compressed) weights to pinned host memory
            # (reference -offload); quantize-then-offload streams 4-8x
            # fewer bytes per step
            self.ffmodel.offload_weights()

        self.rm = RequestManager()
        if self.tokenizer is not None:
            self.rm.register_tokenizer(self.tokenizer)
        else:
            eos = getattr(self.hf_config, "eos_token_id", None)
            self.rm.eos_token_id = eos
        if self.output_file:
            self.rm.register_output_filepath(self.output_file)

        # Draft models must share the verifier's batch geometry so request
        # slots line up across caches (reference RequestManager assumes one
        # BatchConfig shape across llm+ssms).
        for ssm in self.ssms:
            ssm.compile(generation_config=self.generation_config,
                        max_requests_per_batch=max_requests_per_batch,
                        max_seq_length=max_seq_length,
                        max_tokens_per_batch=max_tokens_per_batch,
                        **ffconfig_kwargs)
        return self

    # ------------------------------------------------------------------
    def generate(self, requests_or_prompts: Union[str, Sequence],
                 max_new_tokens: int = 128,
                 max_length: int = 0,
                 timeout_s: Optional[float] = None,
                 tenant: str = "default",
                 priority: int = 0
                 ) -> Union[GenerationResult, List[GenerationResult]]:
        """Generate (reference LLM.generate :407): continuous batching over
        prompts; speculative tree decoding when SSMs are attached.

        ``timeout_s`` bounds each request's wall clock: past it the
        request is cancelled between decode rounds and its result comes
        back with ``timed_out=True`` and the partial output. ``tenant``/
        ``priority`` feed admission control and deadline-aware slot
        scheduling in server mode (serve/admission.py); in server mode
        an over-limit submission raises ``RejectedError``."""
        if self.ffmodel is None:
            raise RuntimeError("call LLM.compile() before generate()")
        single = isinstance(requests_or_prompts, str) or (
            requests_or_prompts and
            isinstance(requests_or_prompts[0], int))
        prompts = [requests_or_prompts] if single else list(requests_or_prompts)
        if not prompts:
            # an empty submission would otherwise enqueue a waiter no
            # generation round ever releases (server mode blocks forever)
            return []
        if self._server is not None:
            # server mode: enqueue into the background loop's continuous
            # batch and block until THIS submission's requests finish;
            # concurrent generate() calls from other threads interleave
            # into the same running batch
            srv = self._server
            guids, ev = srv.submit(prompts, max_new_tokens, max_length,
                                   timeout_s=timeout_s, tenant=tenant,
                                   priority=priority)
            ev.wait()
            if srv._error is not None:
                raise RuntimeError("serving loop died") from srv._error
            missing = [g for g in guids if g not in self.rm.results]
            if missing:
                # stop_server()'s flush window expired before these
                # finished — an explicit error, never a silent drop
                raise RuntimeError(
                    f"server stopped before request(s) {missing} resolved")
        else:
            guids = [self.rm.register_new_request(
                p, max_new_tokens=max_new_tokens,
                max_sequence_length=max_length, timeout_s=timeout_s,
                tenant=tenant, priority=priority) for p in prompts]
            if self.ssms:
                self.rm.generate_spec_infer(
                    self.ffmodel, [s.ffmodel for s in self.ssms],
                    generation_config=self.generation_config)
            else:
                self.rm.generate_incr_decoding(
                    self.ffmodel, generation_config=self.generation_config)
        # prompt order, not completion order (results[i] pairs with prompts[i])
        results = [self.rm.results[g] for g in guids]
        return results[0] if single else results

    def cancel(self, request_id: int) -> bool:
        """Cancel a registered request by guid (C ABI:
        ``ffsv_request_cancel``). The serving loop reaps the flag at the
        next between-rounds seam on every scheduler path; the request's
        result resolves with ``cancelled=True`` and whatever tokens were
        already generated. False when unknown or already finished."""
        if self.rm is None:
            return False
        return self.rm.cancel(request_id)

    # ------------------------------------------------------------------
    def start_server(self, admission=None):
        """Start the background RequestManager server (reference
        serve.py start_server): a daemon thread owns the generation step
        loop and a thread-safe submission queue, so concurrent
        ``generate`` calls interleave into one running continuous batch.
        The device is only ever driven from the server thread.

        ``admission`` (optional) bounds the front door: an
        ``AdmissionPolicy`` (or prebuilt ``AdmissionController``) from
        serve/admission.py — over-limit submissions then raise
        ``RejectedError`` instead of queueing without bound."""
        if self.ffmodel is None:
            raise RuntimeError("call LLM.compile() before start_server()")
        if self._server is None:
            ctrl = admission
            if ctrl is not None:
                from flexflow_tpu.serve.admission import (AdmissionController,
                                                          AdmissionPolicy)

                if isinstance(ctrl, AdmissionPolicy):
                    ctrl = AdmissionController(ctrl)
            self._server = _BackgroundServer(self, admission=ctrl)
            self._server.start()
        return self

    def stop_server(self, flush_timeout_s: Optional[float] = 30.0):
        """Drain outstanding requests and stop the background server:
        flush-with-timeout (``flush_timeout_s`` per phase; None = wait
        forever). If the drain window expires, outstanding requests are
        cancelled — the loops reap cancellations between decode rounds,
        so the second join is bounded by one block — and every waiter is
        resolved rather than silently dropped."""
        srv = self._server
        if srv is not None:
            srv.stop(flush_timeout_s)
            self._server = None
        return self

    # ------------------------------------------------------------------
    def start_metrics_server(self, port: int = 9600,
                             host: str = "127.0.0.1"):
        """Expose the telemetry registry over HTTP: ``GET /metrics``
        (Prometheus text) and ``GET /metrics.json``. Enables telemetry if
        it is not on yet (an endpoint over a dead registry is useless).
        ``port=0`` binds an ephemeral port; the bound port is on the
        returned server object (``.port``) and ``self._metrics_server``.
        """
        from flexflow_tpu.telemetry import (MetricsHTTPServer,
                                            ensure_telemetry, get_telemetry)

        ensure_telemetry()
        if getattr(self, "_metrics_server", None) is None:
            self._metrics_server = MetricsHTTPServer(
                lambda: getattr(get_telemetry(), "registry", None),
                host=host, port=port)
        return self._metrics_server

    def stop_metrics_server(self):
        srv = getattr(self, "_metrics_server", None)
        if srv is not None:
            srv.stop()
            self._metrics_server = None
        return self


class _BackgroundServer:
    """Background serving loop (reference python/flexflow/serve/serve.py
    server semantics). Submitter threads register requests under the
    condition lock and wait on a per-submission event; the server thread
    runs generation rounds whenever work is queued. Requests that arrive
    while a round is in flight join its continuous batch at the next
    slot-fill (RequestManager's loops re-poll ``pending`` every
    iteration), so late submitters share device steps with the batch
    already running.

    Overload safety (serve/admission.py): when an ``admission``
    controller is attached, submissions are admitted or rejected under
    the same lock that registers them, so the queue-depth check and the
    registration are atomic. Realized queue waits from every finished
    round feed back into the controller's windowed p99, which is where
    rejections get their retry-after hint."""

    def __init__(self, llm: "LLM", admission=None):
        self.llm = llm
        self.admission = admission
        self._work = threading.Condition()
        self._stopping = False
        # (remaining-guid-set, event) per submission
        self._waiters: List[Tuple[set, threading.Event]] = []
        # the loop traces every serving program: give its frames one chunk
        self._thread = threading.Thread(
            target=lambda: with_deep_stack(self._run), daemon=True,
            name="flexflow-serve")
        self._error: Optional[BaseException] = None

    def start(self):
        self._thread.start()

    def submit(self, prompts, max_new_tokens: int, max_length: int,
               timeout_s: Optional[float] = None, tenant: str = "default",
               priority: int = 0, trace_id: Optional[str] = None,
               failovers: int = 0, preemptions: int = 0
               ) -> Tuple[List[int], threading.Event]:
        ev = threading.Event()
        with self._work:
            if self._error is not None:
                raise RuntimeError("serving loop died") from self._error
            if self._stopping or not self._thread.is_alive():
                raise RuntimeError(
                    "server is stopping/stopped; submit raced stop_server()")
            if self.admission is not None:
                depth = len(self.llm.rm.pending)
                try:
                    self.admission.admit(tenant, depth, n=len(prompts))
                except Exception as e:
                    tel = self.llm.rm._tel()
                    if tel is not None:
                        tel.note_rejected(tenant,
                                          getattr(e, "reason", "rejected"),
                                          depth)
                    raise
            guids = [self.llm.rm.register_new_request(
                p, max_new_tokens=max_new_tokens,
                max_sequence_length=max_length, timeout_s=timeout_s,
                tenant=tenant, priority=priority, trace_id=trace_id,
                failovers=failovers, preemptions=preemptions)
                for p in prompts]
            self._waiters.append((set(guids), ev))
            self._work.notify_all()
        return guids, ev

    def stop(self, flush_timeout_s: Optional[float] = 30.0):
        with self._work:
            self._stopping = True
            self._work.notify_all()
        self._thread.join(flush_timeout_s)
        if self._thread.is_alive():
            # flush window expired mid-batch: cancel everything still
            # outstanding — the loops reap cancel flags between decode
            # rounds, so this second join is bounded by one block
            rm = self.llm.rm
            for guid in list(rm.inflight):
                rm.cancel(guid)
            self._thread.join(flush_timeout_s)
        # every waiter resolves, even if its guids never produced results
        # (LLM.generate turns a missing result into an explicit error)
        with self._work:
            for _, ev in self._waiters:
                ev.set()
            self._waiters.clear()

    def _run(self):
        rm = self.llm.rm
        while True:
            with self._work:
                while not rm.pending and not self._stopping:
                    self._work.wait(timeout=0.05)
                if self._stopping and not rm.pending:
                    # release any waiters for already-finished guids
                    for _, ev in self._waiters:
                        ev.set()
                    return
            try:
                gen_cfg = getattr(self.llm, "generation_config", None)
                if self.llm.ssms:
                    done = rm.generate_spec_infer(
                        self.llm.ffmodel,
                        [s.ffmodel for s in self.llm.ssms],
                        generation_config=gen_cfg)
                else:
                    done = rm.generate_incr_decoding(
                        self.llm.ffmodel, generation_config=gen_cfg)
            except BaseException as e:       # surface to submitters
                # fail every in-flight AND queued request with this error
                # (each gets a status="error" result), then release all
                # waiters — submitters raise instead of hanging forever.
                # pending/inflight are now empty, so a restarted server
                # starts clean.
                rm.abort_outstanding(e)
                with self._work:
                    self._error = e
                    for _, ev in self._waiters:
                        ev.set()
                    self._waiters.clear()
                raise
            if self.admission is not None:
                with self._work:
                    for res in done or ():
                        if res.queue_wait_s > 0.0:
                            self.admission.observe_queue_wait(
                                res.queue_wait_s)
            with self._work:
                done_guids = set(rm.results)
                fire = []
                keep = []
                for guids, ev in self._waiters:
                    guids -= done_guids
                    (keep if guids else fire).append((guids, ev))
                self._waiters = keep
            for _, ev in fire:
                ev.set()


class SSM(LLM):
    """Small speculative model / draft model (reference serve/serve.py:429)."""

    inference_mode = InferenceMode.BEAM_SEARCH_MODE
