"""The one seam into the program. Nothing else under benchmark/ imports it.

The program cannot today serve what a cell needs, and a benchmark PR may not
edit it, so ONE subclass of `ModelManager` overrides `_load_weights` for
`synthetic://` sources and nothing else:

1. weights from `--seed` (`_synthetic_params` hard-codes `PRNGKey(0)`), made
   by the architecture's own file (`benchmark/archs/`) in the serving layout;
2. the configuration's sizes, depth cut included, from the benchmark's
   configuration file (`_resolve_preset` takes preset names only): the
   architecture's `model_fields` gives them as a plain dict, and only here
   do they become the program's `ModelConfig`;
3. a tokenizer in which every id decodes to text and there is no eos.

The rest of this file is the client's end of the gRPC surface and the
snapshots of the program's own counters.
"""

from __future__ import annotations

import os
from typing import Dict, Iterator, Tuple

from aios_tpu import rpc, services
from aios_tpu.boot.config import AiosConfig, serving_env
from aios_tpu.engine.config import ModelConfig
from aios_tpu.engine.tokenizer import render_chat
from aios_tpu.obs import flightrec
from aios_tpu.proto_gen import runtime_pb2
from aios_tpu.runtime.model_manager import ModelManager
from aios_tpu.runtime.service import serve

from .tokenizer import FixedWidthTokenizer


class SeededManager(ModelManager):
    def __init__(self, arch, config: dict, seed: int, **kw) -> None:
        super().__init__(**kw)
        self._bench_arch = arch
        self._bench_config = config
        self._bench_seed = seed

    def _load_weights(self, name, path, context_length, draft=False):
        if not path.startswith("synthetic://") or draft:
            return super()._load_weights(name, path, context_length, draft)
        arch = self._bench_arch
        cfg = ModelConfig(**arch.model_fields(self._bench_config, context_length))
        params = arch.build_params(arch.dims_of(self._bench_config), self._bench_seed)
        return cfg, params, FixedWidthTokenizer(cfg.vocab_size)


class Served:
    """The system under test, started in this process: the real AIRuntime
    gRPC server on a localhost port, and a stub to it."""

    def __init__(self, arch, config: dict, seed: int) -> None:
        # the environment the default boot config produces (paged KV "auto")
        os.environ.update(serving_env(AiosConfig()))
        assumed = config["assumed"]
        self.name = assumed["served_name"]
        self.manager = SeededManager(arch, config, seed, num_slots=int(assumed["slots"]))
        self.server, self.service, port = serve(
            address="127.0.0.1:0", manager=self.manager, block=False
        )
        self.channel = rpc.insecure_channel(f"127.0.0.1:{port}")
        self.stub = services.AIRuntimeStub(self.channel)
        self.timelines: list = []
        self._listener = self.timelines.append
        flightrec.RECORDER.add_listener(self._listener)
        status = self.stub.LoadModel(runtime_pb2.LoadModelRequest(
            model_name=self.name, model_path=f"synthetic://{self.name}",
            context_length=int(arch.context_length(config)),
        ), timeout=1500)
        if status.status != "ready":
            raise RuntimeError(f"LoadModel -> {status.status!r}")
        self.managed = self.manager.get(self.name)
        self.tokenizer = self.managed.tokenizer

    def stream(self, fields: dict, deadline_s=None) -> Iterator[Tuple[str, bool]]:
        """One request. `deadline_s` is the client's gRPC deadline, which the
        program's admission reads as the request's own (it sheds a request
        whose deadline it judges infeasible); None sends none, as the
        program's own load generator does by default."""
        req = runtime_pb2.InferRequest(**fields)
        for chunk in self.stub.StreamInfer(req, timeout=deadline_s):
            yield chunk.text, chunk.done

    def template_overhead(self) -> Dict[bool, int]:
        """Characters the chat template adds, with and without a system prompt."""
        name = self.managed.config.name
        return {
            True: len(render_chat(name, "p", "s")) - 2,
            False: len(render_chat(name, "p", "")) - 1,
        }

    def prompt_ids(self, prompt: str, system: str) -> list:
        """The ids the model is given for a request, as the service makes them."""
        return self.tokenizer.encode(
            render_chat(self.managed.config.name, prompt, system)
        )

    def counters(self) -> Dict[str, float]:
        out = dict(self.managed.pool.stats())
        batcher = self.managed.batcher
        out["host_gap_seconds"] = batcher.host_gap_seconds
        out["decode_dispatches"] = batcher.decode_dispatches
        return out

    def setup_seconds(self) -> Dict[str, float]:
        return dict(self.managed.setup_seconds)

    def close(self) -> None:
        try:  # the recorder has no remove_listener; leave it as it was found
            flightrec.RECORDER._listeners.remove(self._listener)
        except ValueError:
            pass
        self.channel.close()
        self.server.stop(grace=2).wait(10)
        self.manager.unload_model(self.name)
