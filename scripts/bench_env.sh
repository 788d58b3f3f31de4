# Benchmark environment: source this before running anything under
# benchmarks/ so wall-clock numbers come off a consistent allocator and
# XLA configuration (CI sources it in every benchmark step).
#
#     source scripts/bench_env.sh
#     PYTHONPATH=src python benchmarks/sweep.py ...
#
# Safe to source anywhere: every export preserves a value the caller
# already set, and the tcmalloc preload is skipped when the library is
# not installed.

# tcmalloc: faster malloc for the allocation-heavy NumPy/XLA paths;
# preload only where the distro ships it (same guard either way).
for _tcmalloc in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
                 /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4; do
    if [ -z "${LD_PRELOAD:-}" ] && [ -e "${_tcmalloc}" ]; then
        export LD_PRELOAD="${_tcmalloc}"
    fi
done
unset _tcmalloc

# no tcmalloc large-alloc spam on multi-GB sweep arrays
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"

# silence TF/XLA C++ banner noise in benchmark logs
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# respect a caller/CI-provided XLA_FLAGS (the multi-device jobs force
# --xla_force_host_platform_device_count=8); nothing forced by default.
export XLA_FLAGS="${XLA_FLAGS:-}"
