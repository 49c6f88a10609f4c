"""Flagship TPU-serving example (reference has no model layer — this is the
new capability, SURVEY.md §2.9): a generate endpoint behind the
continuous-batching engine — a demo-sized Llama, or whatever decoder family
the given configuration object belongs to — plus token streaming over
websocket."""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))))

from gofr_tpu import App
from gofr_tpu.config import EnvConfig
from gofr_tpu.models import LlamaConfig, ModelSpec, family_of


def build_app(config=None, *, model_config=None, **engine_kw) -> App:
    """``model_config`` (a served family's config object: ``LlamaConfig``,
    ``Cohere2MoeConfig``, ...) replaces the demo-sized LlamaConfig — and the
    demo's byte tokenizer with it, so prompts, results and streams are token
    ids (a deployment names its own tokenizer in the ModelSpec). The family
    is the one the object belongs to. ``engine_kw`` go through to
    ``serve_model`` (kv_layout, slots, max_len, page_size, ...) on top of
    the demo-sized defaults."""
    import os

    folder = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs")
    app = App(config=config or EnvConfig(folder=folder))

    if model_config is None:
        from gofr_tpu.utils import ByteTokenizer

        # vocab must cover the byte tokenizer's 259 ids; prompts can be raw
        # token-id lists OR strings (encoded through the tokenizer), and
        # results carry decoded text alongside ids.
        cfg, tokenizer = LlamaConfig.tiny(vocab_size=300), ByteTokenizer()
    else:
        cfg, tokenizer = model_config, None
    spec = ModelSpec(family_of(cfg), cfg, task="generate", dtype=cfg.dtype, tokenizer=tokenizer)
    # EOS is disabled here because random weights emit any token — a real
    # checkpoint would keep the tokenizer's eos_token_id (build_engine wires
    # it automatically).
    app.serve_model("lm", spec, **{"slots": 4, "max_len": 64, "eos_token_id": -1, **engine_kw})

    async def generate(ctx):
        # async handler + agenerate: awaits the engine future on the event
        # loop instead of parking a handler thread per in-flight request
        body = ctx.bind(dict)
        return await ctx.agenerate(
            "lm", body["prompt"],
            max_new_tokens=int(body.get("max_new_tokens", 8)),
            temperature=float(body.get("temperature", 0.0)),
            timeout=body.get("timeout", 120),
        )

    def generate_stream(ctx):
        """SSE: tokens arrive as `data:` events while decode is running."""
        from gofr_tpu.http.streaming import StreamingResponse

        body = ctx.bind(dict)
        it = ctx.generate(
            "lm", body["prompt"],
            max_new_tokens=int(body.get("max_new_tokens", 8)),
            temperature=float(body.get("temperature", 0.0)),
            timeout=body.get("timeout", 120),
            stream=True,
        )
        return StreamingResponse(it, event="token")

    def ws_generate(ctx):
        """Websocket: one message per token (websocket.go:37-53 parity)."""
        from gofr_tpu.http.streaming import StreamingResponse

        body = ctx.bind(dict)
        it = ctx.generate(
            "lm", body["prompt"],
            max_new_tokens=int(body.get("max_new_tokens", 8)),
            timeout=body.get("timeout", 120),
            stream=True,
        )
        return StreamingResponse(it)

    app.post("/generate", generate)
    app.post("/generate/stream", generate_stream)
    app.websocket("/ws/generate", ws_generate)
    return app


if __name__ == "__main__":
    build_app().run()
