"""End-to-end Carbon Containers demo: train a ~100M-param-class model (reduced
to CPU scale) for a few hundred steps under a carbon cap, with LIVE
enforcement — duty-cycling, elastic slice migration (real checkpoint ->
reshard -> restore between device subsets), and suspend/resume — while the
grid's carbon intensity follows a realistic diurnal trace.

    PYTHONPATH=src python examples/carbon_train.py [--steps 200]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import tempfile

import jax

from repro.carbon.intensity import TraceProvider
from repro.cluster.slices import SliceFamily, Slice
from repro.compile_cache import enable_compile_cache
from repro.config import CarbonConfig, OptimizerConfig, TrainConfig
from repro.configs import get_arch
from repro.core.carbon_aware_trainer import CarbonAwareTrainer
from repro.core.elastic import ElasticJob
from repro.data.pipeline import markov_stream
from repro.models import get_model
from repro.power.model import LinearPowerModel


def demo_family(n_devices: int) -> tuple:
    """Slice family over local devices: 1/2/4/8 chips, power ∝ chips."""
    sizes = [1, 2, 4, 8]
    sizes = [s for s in sizes if s <= n_devices]
    slices = [Slice(f"cpu-{s}", s / sizes[len(sizes)//2],
                    LinearPowerModel(40.0 * s, 110.0 * s), chips=s)
              for s in sizes]
    fam = SliceFamily(slices, baseline_idx=len(sizes) // 2)
    devs = jax.devices()
    slice_devs = [devs[:s.chips] for s in fam.slices]
    return fam, slice_devs


def main():
    enable_compile_cache()
    steps = 200
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])

    spec = get_arch("smollm-135m")
    model = get_model(spec.smoke)
    tcfg = TrainConfig(seq_len=64, global_batch=8, steps=steps,
                       optimizer=OptimizerConfig(lr=2e-3, warmup_steps=10,
                                                 total_steps=steps),
                       log_every=0)
    fam, slice_devs = demo_family(len(jax.devices()))
    ckpt = tempfile.mkdtemp(prefix="lxcc_")
    job = ElasticJob(model, tcfg, ckpt)
    job.start(slice_devs[fam.baseline_idx])

    ccfg = CarbonConfig(target_rate=45.0, policy="energy", region="NL",
                        interval_s=300.0)
    # each train step advances the sim clock by 90 s -> 200 steps ≈ 5 h of
    # grid variation; demand varies with the duty cycle the policy sets
    step_flops = 6.0 * model.param_count() * tcfg.seq_len * tcfg.global_batch
    # make MFU meaningful on fake 'chips': pretend peak = what we achieve
    trainer = CarbonAwareTrainer(
        job=job, family=fam, slice_devices=slice_devs,
        carbon=TraceProvider.for_region(ccfg.region, seed=4),
        cfg=ccfg, step_flops=step_flops,
        step_tokens=tcfg.seq_len * tcfg.global_batch,
        peak_flops_per_chip=step_flops / 60.0,   # demo: ~60 s/step at MFU=1
        sim_seconds_per_step=90.0)

    data = markov_stream(spec.smoke.vocab_size, tcfg.seq_len,
                         tcfg.global_batch, temperature=0.2)
    print(f"target C = {ccfg.target_rate} g/hr, region {ccfg.region}, "
          f"policy {ccfg.policy}")
    out = trainer.run(data, steps)
    print(f"\ncompleted {out['steps']} steps with "
          f"{len(out['migrations'])} live migrations")
    print("timeline (one row per monitoring interval):")
    for log in out["logs"][:: max(1, len(out["logs"]) // 12)]:
        bar = "#" * int(log.carbon_rate / 3)
        print(f"  t={log.t/3600:5.2f}h  c={log.carbon_intensity:4.0f} g/kWh  "
              f"slice={log.slice_name:6s} duty={log.duty:4.2f} "
              f"C={log.carbon_rate:6.1f} g/hr {bar}")
    rates = [l.carbon_rate for l in out["logs"]]
    print(f"\navg C(t) = {sum(rates)/len(rates):.1f} g/hr "
          f"(target {ccfg.target_rate}) — "
          f"{'ENFORCED' if sum(rates)/len(rates) <= ccfg.target_rate else 'EXCEEDED'}")


if __name__ == "__main__":
    main()
