# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
#   bench_table1    Table 1 (power + kFPS/W per [W:A] + published baselines)
#   bench_fig8      Fig. 8  (LeNet layer-wise power breakdown)
#   bench_fig9      Fig. 9  (VGG9 breakdown, DAC share, CA L1 reduction)
#   bench_fig10     Fig. 10 (execution time, AlexNet/VGG16)
#   bench_accuracy  Table 1 accuracy axis (QAT trend on synthetic digits)
#   bench_kernels   Pallas kernels vs oracles
#   bench_pipeline  eager vs compiled device pipeline frames/s (core.plan)
#   bench_imaging   imaging pipelines frames/s + PSNR/SSIM per scheme
#   bench_serving   serving runtime: offered-load sweep + batching ablation
#   bench_obs       observability overhead: disabled-path cost vs raw executor
#   bench_analysis  plan-verifier compile overhead + concurrency-lint cost

import os
import sys

# Tuned CPU launch env: silence the XLA/TF C++ banner before jax loads.
# scripts/ci.sh sets the same knob and additionally preloads tcmalloc when
# it is installed (LD_PRELOAD has to be set before the process starts, so
# it cannot be applied from here).
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")


def main() -> None:
    # bench_serving's device-pool ablation runs on four local devices in
    # this process; on CPU they are virtual ones, fixed before the JAX
    # backend starts (the flag does not touch TPU devices)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_analysis, bench_table1, bench_fig8,
                            bench_fig9, bench_fig10, bench_accuracy,
                            bench_kernels, bench_lm_photonic, bench_obs,
                            bench_pipeline, bench_imaging, bench_serving)
    bench_table1.run()
    bench_fig8.run()
    bench_fig9.run()
    bench_fig10.run()
    quick = "--quick" in sys.argv
    bench_accuracy.run(steps=30 if quick else 40)
    bench_kernels.run(sizes=(64, 128) if quick
                      else bench_kernels.SWEEP_SIZES)
    bench_lm_photonic.run()
    bench_pipeline.run(batches=(1, 8) if quick else bench_pipeline.BATCHES)
    bench_imaging.run(pipelines=("edge_detect", "compress_recon")
                      if quick else None)
    bench_serving.run(quick=quick)
    bench_obs.run()
    bench_analysis.run()


if __name__ == '__main__':
    main()
