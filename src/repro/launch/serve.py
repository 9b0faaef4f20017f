"""Serving driver: prefill a batch of prompts, decode N tokens.

CPU-friendly demonstration of the serving runtime (the same step
functions the dry-run lowers at production shapes):

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \
      --batch 4 --prompt-len 64 --gen 32

``--trace DIR`` records one fenced span per prefill and per decoded
token (``repro.telemetry``) and writes the JSONL event log plus a
Perfetto-loadable Chrome trace into DIR — the serving analogue of the
train driver's ``--trace`` (see ``docs/observability.md``).
"""
from __future__ import annotations

import argparse
import time


def build_parser() -> argparse.ArgumentParser:
    """The driver's CLI. Separate from :func:`main` so tooling
    (``repro.analysis.docs_lint``) can verify documented flags against
    the real parser without importing jax."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--preset", default="tiny", choices=("tiny", "full"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="", metavar="DIR",
                    help="record a fenced span per prefill / decoded "
                         "token; write events.jsonl + trace.json "
                         "(chrome://tracing / Perfetto) into DIR")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    from repro.launch import runtime

    runtime.force_host_devices(args.data_par * args.model_par)
    import jax
    import jax.numpy as jnp
    import numpy as np

    runtime.init_compile_cache()
    print(runtime.device_line())

    from repro.configs.registry import get_config, get_smoke_config
    from repro.data.pipeline import SyntheticCorpus
    from repro.dist import serve as sv
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model
    from repro.telemetry import StepTimer, TraceRecorder

    cfg = (
        get_smoke_config(args.arch) if args.preset == "tiny"
        else get_config(args.arch)
    )
    model = Model(cfg)
    mesh = make_mesh((args.data_par, args.model_par), ("data", "model"))
    rules = shd.serve_rules(mesh, cfg)
    if args.batch % args.data_par:
        raise SystemExit("batch must divide data_par")

    recorder = None
    if args.trace:
        recorder = TraceRecorder(meta=dict(
            arch=args.arch, preset=args.preset, batch=args.batch,
            prompt_len=args.prompt_len, gen=args.gen,
            data_par=args.data_par, model_par=args.model_par,
        ))
    timer = StepTimer(recorder)

    max_len = args.prompt_len + args.gen
    params = model.init(jax.random.key(args.seed))
    corpus = SyntheticCorpus(cfg.vocab_size, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    prompts = np.stack(
        [corpus.sample(rng, args.prompt_len) for _ in range(args.batch)]
    ).astype(np.int32)

    prefill = jax.jit(sv.make_prefill_step(model, rules, max_len=max_len))
    decode = jax.jit(sv.make_decode_step(model, rules, max_len=max_len))

    with jax.set_mesh(mesh):
        caches = model.init_cache(args.batch, max_len)
        t0 = time.time()
        kwargs = {}
        if cfg.frontend == "audio":
            kwargs["encoder_frames"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.frontend_dim or cfg.d_model),
                jnp.bfloat16,
            )
        with timer.phase("prefill", cat="serve",
                         tokens=args.batch * args.prompt_len) as sp:
            logits, caches = prefill(
                params, jnp.asarray(prompts), caches, **kwargs
            )
            sp.fence(logits)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0

        out_tokens = [jnp.argmax(logits[:, -1, :], axis=-1)]
        t0 = time.time()
        for i in range(args.gen - 1):
            tok = out_tokens[-1][:, None].astype(jnp.int32)
            with timer.phase("decode", cat="serve", step=i) as sp:
                logits, caches = decode(
                    params, tok, caches, jnp.int32(args.prompt_len + i)
                )
                out_tokens.append(jnp.argmax(logits[:, -1, :], axis=-1))
                sp.fence(out_tokens[-1])
        jax.block_until_ready(out_tokens[-1])
        t_decode = time.time() - t0

    gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms   decode: "
          f"{t_decode/max(args.gen-1,1)*1e3:.1f} ms/token")
    print("generated token ids (first request):", gen[0][:16], "...")
    assert np.isfinite(gen).all()

    if recorder is not None:
        jsonl_path, chrome_path = recorder.flush(args.trace)
        print(f"wrote trace: {jsonl_path} + {chrome_path} "
              f"({len(recorder.events())} events)")


if __name__ == "__main__":
    main()
