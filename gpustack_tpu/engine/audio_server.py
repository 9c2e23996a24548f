"""Audio transcription server: OpenAI ``/v1/audio/transcriptions``.

The VoxBox role of the reference (worker/backends/vox_box.py:23 — audio
models served behind the same OpenAI surface). One process owns a
Whisper-class model (models/whisper.py); requests carry WAV audio as
multipart form data; transcription runs encode + jitted greedy decode on
the accelerator. Launched by the worker's serve manager exactly like the
LLM engine (worker/backends.py picks this entrypoint for audio-category
models) and fronted by the same authenticated worker proxy.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import time
import uuid

from aiohttp import web

from gpustack_tpu.observability.tracing import LOG_FORMAT

logger = logging.getLogger(__name__)


class AudioEngine:
    """Owns model params + a serialized synthesis/transcription executor.

    One process serves one audio model: STT (Whisper-class,
    ``modality="stt"``) or TTS (FastSpeech-class, ``modality="tts"``) —
    together covering the reference's VoxBox role
    (worker/backends/vox_box.py:23 does both)."""

    def __init__(self, cfg, params, model_dir: str = "", modality: str = "stt"):
        self.cfg = cfg
        self.params = params
        self.model_dir = model_dir
        self.modality = modality
        self.tokenizer = self._load_tokenizer(model_dir)
        self._lock = asyncio.Lock()
        self.requests = 0
        self.audio_seconds = 0.0

    @staticmethod
    def _load_tokenizer(model_dir: str):
        if model_dir:
            try:
                from transformers import AutoTokenizer

                return AutoTokenizer.from_pretrained(model_dir)
            except Exception:
                logger.warning(
                    "no HF tokenizer in %s; using byte fallback", model_dir
                )
        from gpustack_tpu.engine.tokenizer import ByteTokenizer

        return ByteTokenizer()

    def _task_prompt_ids(self, task: str, language: str = "") -> tuple:
        """Whisper task/language conditioning: force ``<|xx|>`` (the
        OpenAI ``language`` form field, ISO 639-1) and ``<|translate|>``
        tokens after start-of-transcript (reference VoxBox serves both
        /v1/audio endpoints through the same model). Tokenizers without
        whisper task tokens (hermetic byte fallback) condition nothing."""
        convert = getattr(
            getattr(self.tokenizer, "_tok", None),
            "convert_tokens_to_ids", None,
        )
        if convert is None:
            if language:
                raise ValueError(
                    f"this model's tokenizer has no language tokens; "
                    f"cannot honor language={language!r}"
                )
            return ()
        unk = getattr(self.tokenizer._tok, "unk_token_id", None)

        def tid_of(token: str):
            tid = convert(token)
            return tid if tid is not None and tid != unk else None

        ids = []
        if language:
            lang_tid = tid_of(f"<|{language.lower()}|>")
            if lang_tid is None:
                # an unhonorable hint must not be silently dropped —
                # the client would believe it was applied
                raise ValueError(
                    f"unsupported language {language!r} (ISO 639-1 "
                    "code the model's tokenizer knows, e.g. 'en')"
                )
            ids.append(lang_tid)
        if task == "translate":
            tr = tid_of("<|translate|>")
            if tr is not None:
                ids.append(tr)
        elif ids:
            # whisper's canonical conditioning is sot→language→task:
            # with a forced language the task token must be forced too,
            # or greedy decode may pick <|translate|> on its own
            tr = tid_of("<|transcribe|>")
            if tr is not None:
                ids.append(tr)
        return tuple(ids)

    async def transcribe(
        self, wav_bytes: bytes, task: str = "transcribe",
        language: str = "",
    ) -> dict:
        from gpustack_tpu.models.audio import decode_wav, features_for_model
        from gpustack_tpu.models.whisper import greedy_transcribe

        audio = decode_wav(wav_bytes)
        mel = features_for_model(audio, self.cfg)
        prompt_ids = self._task_prompt_ids(task, language)
        start = time.monotonic()
        # one transcription at a time per process: decode is a tight
        # jitted loop; concurrency comes from replicas
        async with self._lock:
            ids = await asyncio.get_event_loop().run_in_executor(
                None,
                lambda: greedy_transcribe(
                    self.params, self.cfg, mel, prompt_ids=prompt_ids
                ),
            )
        text = self.tokenizer.decode(ids)
        self.requests += 1
        self.audio_seconds += len(audio) / 16000.0
        return {
            "text": text,
            "duration_s": round(len(audio) / 16000.0, 2),
            "latency_ms": round((time.monotonic() - start) * 1e3, 1),
        }

    async def speak(
        self, text: str, voice: str = "", speed: float = 1.0
    ) -> bytes:
        """Text → WAV bytes via the jitted synth + host Griffin-Lim."""
        from gpustack_tpu.models.tts import (
            pcm_to_wav_bytes,
            synthesize,
            voice_index,
        )

        ids = self.tokenizer.encode(text)
        if not ids:
            raise ValueError("input text is empty")
        async with self._lock:
            audio = await asyncio.get_event_loop().run_in_executor(
                None,
                lambda: synthesize(
                    self.params, self.cfg, ids,
                    voice=voice_index(voice, self.cfg), speed=speed,
                ),
            )
        self.requests += 1
        self.audio_seconds += len(audio) / self.cfg.sample_rate
        return pcm_to_wav_bytes(audio, self.cfg.sample_rate)


class AudioServer:
    def __init__(self, engine: AudioEngine, model_name: str = ""):
        self.engine = engine
        self.model_name = model_name or engine.cfg.name
        self.app = web.Application(client_max_size=256 * 2**20)
        self.app.add_routes(
            [
                web.post(
                    "/v1/audio/transcriptions", self.transcriptions
                ),
                web.post(
                    "/v1/audio/translations", self.transcriptions
                ),
                web.post("/v1/audio/speech", self.speech),
                web.get("/healthz", self.healthz),
                web.get("/metrics", self.metrics),
            ]
        )

    async def healthz(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "status": "ok",
                "model": self.model_name,
                "modality": f"audio/{self.engine.modality}",
                "requests": self.engine.requests,
            }
        )

    async def speech(self, request: web.Request) -> web.Response:
        """OpenAI ``/v1/audio/speech``: JSON {input, voice, speed} → WAV
        bytes (reference VoxBox serves TTS on the same path)."""
        if self.engine.modality != "tts":
            return web.json_response(
                {"error": f"model {self.model_name} is not a TTS model"},
                status=400,
            )
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            return web.json_response(
                {"error": "invalid JSON body"}, status=400
            )
        text = body.get("input")
        if not isinstance(text, str) or not text.strip():
            return web.json_response(
                {"error": "missing 'input'"}, status=400
            )
        fmt = body.get("response_format") or "wav"
        if fmt not in ("wav", "pcm"):
            return web.json_response(
                {"error": f"unsupported response_format {fmt!r}; this "
                 "engine produces wav/pcm"}, status=400
            )
        speed = body.get("speed")
        if speed is None:
            speed = 1.0
        if isinstance(speed, bool) or not isinstance(speed, (int, float)):
            return web.json_response(
                {"error": "'speed' must be a number"}, status=400
            )
        if not 0.25 <= speed <= 4.0:
            return web.json_response(
                {"error": "'speed' must be between 0.25 and 4.0"},
                status=400,
            )
        try:
            wav = await self.engine.speak(
                text, voice=str(body.get("voice") or ""), speed=speed
            )
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        if fmt == "pcm":
            # strip the 44-byte RIFF header: raw 16-bit mono PCM
            return web.Response(
                body=wav[44:], content_type="application/octet-stream"
            )
        return web.Response(body=wav, content_type="audio/wav")

    async def metrics(self, request: web.Request) -> web.Response:
        return web.Response(
            text=(
                "# TYPE gpustack_tpu_audio_requests_total counter\n"
                f"gpustack_tpu_audio_requests_total {self.engine.requests}\n"
                "# TYPE gpustack_tpu_audio_seconds_total counter\n"
                f"gpustack_tpu_audio_seconds_total "
                f"{self.engine.audio_seconds:.2f}\n"
            )
        )

    async def transcriptions(self, request: web.Request) -> web.Response:
        if self.engine.modality != "stt":
            return web.json_response(
                {"error": f"model {self.model_name} is not an STT model"},
                status=400,
            )
        if not request.content_type.startswith("multipart/"):
            return web.json_response(
                {"error": "multipart/form-data with a 'file' part required"},
                status=400,
            )
        wav = None
        fmt = "json"
        language = ""
        async for part in await request.multipart():
            if part.name == "file":
                wav = await part.read(decode=False)
            elif part.name == "response_format":
                fmt = (await part.text()).strip() or "json"
            elif part.name == "language":
                language = (await part.text()).strip()
        if not wav:
            return web.json_response(
                {"error": "missing 'file' part"}, status=400
            )
        import wave as _wave

        task = (
            "translate" if request.path.endswith("/translations")
            else "transcribe"
        )
        try:
            result = await self.engine.transcribe(
                wav, task=task, language=language
            )
        except ValueError as e:
            # covers undecodable audio AND unhonorable language hints —
            # the exception message says which
            return web.json_response({"error": str(e)}, status=400)
        except (_wave.Error, EOFError) as e:
            return web.json_response(
                {"error": f"invalid audio: {e}"}, status=400
            )
        if fmt == "text":
            return web.Response(text=result["text"])
        return web.json_response(
            {
                "id": f"transcr-{uuid.uuid4().hex[:12]}",
                "object": (
                    "audio.translation" if task == "translate"
                    else "audio.transcription"
                ),
                "model": self.model_name,
                **result,
            }
        )


def build_audio_engine_from_args(args) -> AudioEngine:
    forced = os.environ.get("GPUSTACK_TPU_PLATFORM")
    import jax

    if forced:
        jax.config.update("jax_platforms", forced)
    from gpustack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from gpustack_tpu.models.tts import TTS_PRESETS, init_tts_params
    from gpustack_tpu.models.whisper import (
        WHISPER_PRESETS,
        config_from_hf_whisper,
        init_whisper_params,
    )

    if args.model_dir:
        with open(os.path.join(args.model_dir, "config.json")) as f:
            hf_cfg = json.load(f)
        if hf_cfg.get("model_type") in ("tts", "fastspeech"):
            # our own checkpoint format for the in-repo TTS: config.json
            # names a preset; params load from a .npz next to it
            from gpustack_tpu.engine.weights import load_npz_params

            cfg = TTS_PRESETS[hf_cfg.get("preset", "tts-base")]
            params = load_npz_params(
                os.path.join(args.model_dir, "params.npz"),
                lambda: init_tts_params(cfg, jax.random.key(0)),
            )
            return AudioEngine(
                cfg, params, model_dir=args.model_dir, modality="tts"
            )
        cfg = config_from_hf_whisper(hf_cfg)
        from gpustack_tpu.engine.weights import load_whisper_params

        params = load_whisper_params(cfg, args.model_dir)
        return AudioEngine(cfg, params, model_dir=args.model_dir)
    if args.preset in TTS_PRESETS:
        cfg = TTS_PRESETS[args.preset]
        params = init_tts_params(cfg, jax.random.key(0))
        return AudioEngine(cfg, params, modality="tts")
    cfg = WHISPER_PRESETS[args.preset]
    params = init_whisper_params(cfg, jax.random.key(0))
    return AudioEngine(cfg, params)


def main(argv=None) -> None:
    p = argparse.ArgumentParser("gpustack-tpu audio server")
    p.add_argument("--model-dir", default="")
    p.add_argument("--preset", default="whisper-large-v3")
    p.add_argument("--served-name", default="")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9000)
    # accepted for launcher compatibility; unused by the audio engine
    p.add_argument("--max-slots", type=int, default=1)
    p.add_argument("--max-seq-len", type=int, default=448)
    p.add_argument("--quantization", default="")
    p.add_argument("--mesh-plan", default="")
    args, _ = p.parse_known_args(argv)

    logging.basicConfig(level=logging.INFO, format=LOG_FORMAT)
    engine = build_audio_engine_from_args(args)
    server = AudioServer(engine, model_name=args.served_name or None)
    web.run_app(server.app, host=args.host, port=args.port)


if __name__ == "__main__":
    main()
