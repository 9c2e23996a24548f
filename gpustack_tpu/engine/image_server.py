"""Image generation server: OpenAI ``/v1/images/generations``.

The image half of the reference's VoxBox role (worker/backends/
vox_box.py:23 — SD-family models behind the OpenAI images API; BASELINE
config 5 pairs SDXL with Whisper). One process owns a latent-diffusion
pipeline (models/diffusion.py); sampling runs the whole denoising loop
as a single jitted XLA program per (size, steps) bucket. Launched by the
worker's serve manager like the other engines and fronted by the same
authenticated worker proxy.
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import io
import json
import logging
import os
import time
import uuid
from typing import Optional

from aiohttp import web

from gpustack_tpu.observability.tracing import LOG_FORMAT

logger = logging.getLogger(__name__)

SIZE_CHOICES = (256, 512, 768, 1024)


def _png_bytes(arr) -> bytes:
    """[H, W, 3] float in [0,1] -> PNG bytes."""
    import numpy as np
    from PIL import Image

    u8 = (np.asarray(arr) * 255.0 + 0.5).astype("uint8")
    buf = io.BytesIO()
    Image.fromarray(u8).save(buf, format="PNG")
    return buf.getvalue()


class ImageEngine:
    """Owns pipeline params + a serialized sampling executor."""

    def __init__(self, cfg, params, model_dir: str = ""):
        self.cfg = cfg
        self.params = params
        self.model_dir = model_dir
        self.tokenizer = self._load_tokenizer(model_dir)
        self.tokenizer2 = self._load_tokenizer(model_dir, "tokenizer_2") \
            if cfg.text2_dim else None
        self._lock = asyncio.Lock()
        self.requests = 0
        self.images = 0

    @staticmethod
    def _load_tokenizer(model_dir: str, sub: str = "tokenizer"):
        if model_dir and os.path.isdir(os.path.join(model_dir, sub)):
            try:
                from transformers import AutoTokenizer

                return AutoTokenizer.from_pretrained(
                    os.path.join(model_dir, sub)
                )
            except Exception:
                logger.warning(
                    "no HF tokenizer under %s/%s; using byte fallback",
                    model_dir, sub,
                )
        from gpustack_tpu.engine.tokenizer import ByteTokenizer

        return ByteTokenizer()

    def _tokens(self, prompt: str, tokenizer) -> list:
        import numpy as np

        T = self.cfg.max_text_len
        try:
            ids = tokenizer(
                prompt, truncation=True, max_length=T, padding="max_length"
            )["input_ids"]
        except TypeError:
            ids = tokenizer.encode(prompt)[: T]
            ids = ids + [0] * (T - len(ids))
        return np.asarray([ids], dtype=np.int32)

    def _generate_sync(self, prompt: str, negative: str, n: int,
                       size: int, steps: int, guidance: float, seed: int):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from gpustack_tpu.models.diffusion import sample_images

        cond = np.repeat(self._tokens(prompt, self.tokenizer), n, axis=0)
        uncond = np.repeat(self._tokens(negative, self.tokenizer), n, axis=0)
        kwargs = {}
        if self.cfg.text2_dim:
            kwargs["cond_tokens2"] = jnp.asarray(
                np.repeat(self._tokens(prompt, self.tokenizer2), n, axis=0)
            )
            kwargs["uncond_tokens2"] = jnp.asarray(
                np.repeat(self._tokens(negative, self.tokenizer2), n, axis=0)
            )
        imgs = sample_images(
            self.params, self.cfg, jax.random.key(seed),
            jnp.asarray(cond), jnp.asarray(uncond),
            steps=steps, guidance=guidance, height=size, width=size,
            **kwargs,
        )
        return jax.device_get(imgs)

    async def generate(self, prompt: str, negative: str = "", n: int = 1,
                       size: int = 0, steps: int = 30,
                       guidance: float = 7.5,
                       seed: Optional[int] = None) -> list:
        size = size or self.cfg.image_size
        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")
        start = time.monotonic()
        # one sampling run at a time per process (the TPU is busy for the
        # whole denoise loop); concurrency comes from replicas
        async with self._lock:
            imgs = await asyncio.get_event_loop().run_in_executor(
                None,
                lambda: self._generate_sync(
                    prompt, negative, n, size, steps, guidance, seed
                ),
            )
        self.requests += 1
        self.images += len(imgs)
        logger.info(
            "generated %d image(s) %dx%d steps=%d in %.1fs",
            len(imgs), size, size, steps, time.monotonic() - start,
        )
        return [_png_bytes(img) for img in imgs]


class ImageServer:
    def __init__(self, engine: ImageEngine, model_name: str = ""):
        self.engine = engine
        self.model_name = model_name or engine.cfg.name
        self.app = web.Application(client_max_size=64 * 2**20)
        self.app.add_routes([
            web.post("/v1/images/generations", self.generations),
            web.get("/healthz", self.healthz),
            web.get("/metrics", self.metrics),
        ])

    async def healthz(self, request: web.Request) -> web.Response:
        return web.json_response({
            "status": "ok",
            "model": self.model_name,
            "modality": "image",
            "requests": self.engine.requests,
            "images": self.engine.images,
        })

    async def metrics(self, request: web.Request) -> web.Response:
        return web.Response(text=(
            "# TYPE gpustack_tpu_image_requests_total counter\n"
            f"gpustack_tpu_image_requests_total {self.engine.requests}\n"
            "# TYPE gpustack_tpu_images_generated_total counter\n"
            f"gpustack_tpu_images_generated_total {self.engine.images}\n"
        ))

    async def generations(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except (ValueError, UnicodeDecodeError):
            return web.json_response({"error": "invalid JSON"}, status=400)
        prompt = body.get("prompt") or ""
        if not prompt:
            return web.json_response(
                {"error": "'prompt' is required"}, status=400
            )
        try:
            n = min(int(body.get("n", 1) or 1), 4)
            steps = max(1, min(int(body.get("steps", 30) or 30), 100))
            guidance = float(body.get("guidance_scale", 7.5) or 7.5)
            seed = body.get("seed")
            seed = int(seed) if seed is not None else None
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": f"bad numeric parameter: {e}"}, status=400
            )
        size_str = body.get("size") or ""
        size = 0
        if size_str:
            parts = str(size_str).lower().split("x")
            try:
                dims = [int(p) for p in parts]
            except ValueError:
                return web.json_response(
                    {"error": f"bad size {size_str!r}"}, status=400
                )
            if len(set(dims)) != 1:
                return web.json_response(
                    {"error": "only square sizes are supported"},
                    status=400,
                )
            size = dims[0]
            if size not in SIZE_CHOICES:
                return web.json_response(
                    {"error": f"size must be one of "
                     f"{['%dx%d' % (s, s) for s in SIZE_CHOICES]}"},
                    status=400,
                )
            if size > self.engine.cfg.image_size:
                return web.json_response(
                    {"error": f"size {size} exceeds this model's native "
                     f"{self.engine.cfg.image_size}"},
                    status=400,
                )
        try:
            pngs = await self.engine.generate(
                prompt,
                negative=body.get("negative_prompt") or "",
                n=n, size=size, steps=steps, guidance=guidance,
                seed=seed,
            )
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({
            "created": int(time.time()),
            "id": f"img-{uuid.uuid4().hex[:12]}",
            "data": [{"b64_json": base64.b64encode(p).decode()} for p in pngs],
        })


def build_image_engine_from_args(args) -> ImageEngine:
    forced = os.environ.get("GPUSTACK_TPU_PLATFORM")
    import jax

    if forced:
        jax.config.update("jax_platforms", forced)
    from gpustack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from gpustack_tpu.models.diffusion import (
        DIFFUSION_PRESETS,
        config_from_diffusers,
        init_diffusion_params,
    )

    if args.model_dir:
        cfg = config_from_diffusers(args.model_dir)
        from gpustack_tpu.engine.image_weights import load_diffusion_params

        params = load_diffusion_params(cfg, args.model_dir)
    else:
        cfg = DIFFUSION_PRESETS[args.preset]
        params = init_diffusion_params(cfg, jax.random.key(0))
    return ImageEngine(cfg, params, model_dir=args.model_dir)


def main(argv=None) -> None:
    p = argparse.ArgumentParser("gpustack-tpu image server")
    p.add_argument("--model-dir", default="")
    p.add_argument("--preset", default="sd15-shaped")
    p.add_argument("--served-name", default="")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9000)
    # accepted for launcher compatibility; unused by the image engine
    p.add_argument("--max-slots", type=int, default=1)
    p.add_argument("--max-seq-len", type=int, default=77)
    p.add_argument("--quantization", default="")
    p.add_argument("--mesh-plan", default="")
    args, _ = p.parse_known_args(argv)

    logging.basicConfig(level=logging.INFO, format=LOG_FORMAT)
    engine = build_image_engine_from_args(args)
    server = ImageServer(engine, model_name=args.served_name or None)
    web.run_app(server.app, host=args.host, port=args.port)


if __name__ == "__main__":
    main()
