"""Quickstart: train a small model, checkpoint it, and serve from it.

    PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.config import OptimizerConfig, TrainConfig
from repro.configs import get_arch
from repro.data.pipeline import markov_stream
from repro.models import get_model
from repro.serve.engine import ServeEngine, throughput_tokens_per_s
from repro.train import checkpoint as CKPT
from repro.train import loop as TL


def main():
    enable_compile_cache()
    # 1. pick an assigned architecture (reduced config for CPU)
    spec = get_arch("smollm-135m")
    model = get_model(spec.smoke)
    print(f"arch={spec.arch_id} (smoke): {model.param_count():,} params")

    # 2. train on a learnable synthetic stream
    tcfg = TrainConfig(seq_len=64, global_batch=8, steps=60, log_every=20,
                       optimizer=OptimizerConfig(lr=3e-3, warmup_steps=10,
                                                 total_steps=60))
    data = markov_stream(spec.smoke.vocab_size, tcfg.seq_len,
                         tcfg.global_batch, temperature=0.2)
    out = TL.run(model, tcfg, data)
    print(f"loss: {out['history'][0]['loss']:.3f} -> "
          f"{out['history'][-1]['loss']:.3f}")

    # 3. checkpoint + restore
    with tempfile.TemporaryDirectory() as d:
        info = CKPT.save(d, out["state"], step=tcfg.steps)
        print(f"checkpoint: {info['bytes']/1e6:.1f} MB in {info['total_s']*1e3:.0f} ms")

    # 4. serve a few generations from the trained params
    engine = ServeEngine(model)
    engine.params = out["state"]["params"]
    prompts = np.random.default_rng(0).integers(
        0, spec.smoke.vocab_size, (4, 16)).astype(np.int32)
    gen = engine.generate(prompts, 12)
    tp = throughput_tokens_per_s(gen["stats"])
    print(f"generated {gen['tokens'].shape}; decode {tp['decode_tok_s']:.0f} tok/s")
    print("sample:", gen["tokens"][0].tolist())


if __name__ == "__main__":
    main()
