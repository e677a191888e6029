"""Device-aware runtime configuration: the single place platform, precision,
and Pallas-kernel execution settings are decided.

Two halves:

1. **Process/environment helpers** (`set_platform`, `jax_enable_x64`,
   `set_host_device_count`) — thin, idempotent wrappers over the jax config
   and the XLA flag environment, in the spirit of the upstream config
   modules these knobs usually hide in. They must run before jax touches a
   backend; `set_host_device_count` in particular only takes effect if no
   device was initialized yet.

2. **`KernelConfig`** — the one record every Pallas entry point consults.
   Every kernel in `repro.kernels` takes `interpret=None` / `*_block=None`
   and resolves the effective value here, so "run compiled on this TPU with
   these tile sizes" is configured ONCE (env vars, CLI flags, or
   `set_kernel_config`) instead of being a hard-coded `interpret=True`
   default scattered across ten signatures.

Resolution order for the process-wide config:

- an explicit `set_kernel_config(...)` call (serve.py/train.py flags land
  here via `apply_device_args`),
- else environment variables: ``REPRO_INTERPRET`` (``auto`` | ``0``/
  ``false`` | ``1``/``true``), ``REPRO_BLOCK_ROWS``, ``REPRO_BLOCK_IDS``,
  ``REPRO_VMEM_MB``,
- else defaults: ``interpret=None`` (auto: compiled iff an accelerator
  backend is present, interpret on CPU), 256-row bank tiles, 512-id
  blocks, a 16 MiB per-core VMEM budget.

``interpret`` is tri-state on purpose: ``None`` means "decide from the
platform at call time", which is what lets the same binary run compiled on
TPU and interpreted in the CPU CI container with zero flags.

VMEM-aware tile sizing (`fused_lookup_block`, `fit_block_rows`) lives here
too: the int8 fused-lookup kernel carries a (B, n_block) one-hot and a
(B, D) accumulator in VMEM, so a serving batch of >4k ids with the old fixed
n_block=512 would blow the ~16 MiB budget on a real core — the helpers
shrink the bank tile until the working set fits instead of failing (or
silently spilling) on device.
"""
from __future__ import annotations

import os
import threading
from typing import NamedTuple, Optional

import jax

DEFAULT_VMEM_BYTES = 16 * 2 ** 20      # per-core VMEM on current TPUs
DEFAULT_BLOCK_ROWS = 256               # bank-tile rows (streamed kernels)
DEFAULT_BLOCK_IDS = 512                # id-block for gather-style kernels

_GPU_XLA_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
)


# ---------------------------------------------------------------------------
# process/environment helpers
# ---------------------------------------------------------------------------

def set_platform(platform: str) -> None:
    """Pin jax to ``cpu`` | ``gpu`` | ``tpu``. Must run before any jax
    computation touches a backend. On GPU, also appends the XLA perf flags
    the stock install leaves off (idempotent)."""
    if platform not in ("cpu", "gpu", "tpu"):
        raise ValueError(f"unknown platform {platform!r} "
                         "(want cpu | gpu | tpu)")
    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        flags = os.environ.get("XLA_FLAGS", "")
        missing = [f for f in _GPU_XLA_FLAGS if f not in flags]
        if missing:
            os.environ["XLA_FLAGS"] = " ".join([flags, *missing]).strip()


def jax_enable_x64(enable: bool = True) -> None:
    """Toggle 64-bit mode. The KB state is fp32/int8 by design, so this is
    for host-side analysis paths, not the serving kernels."""
    jax.config.update("jax_enable_x64", bool(enable))


def set_host_device_count(n: int) -> None:
    """Force ``n`` host CPU devices via XLA_FLAGS — how the sharded backend
    is exercised without a real mesh. Only effective before the CPU backend
    initializes; calling it late is a silent no-op at the jax level, so we
    do not pretend otherwise here."""
    if n < 1:
        raise ValueError(f"host device count must be >= 1, got {n}")
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    os.environ["XLA_FLAGS"] = " ".join(flags + [flag]).strip()


def default_backend() -> str:
    """The platform jax actually selected (``cpu`` | ``gpu`` | ``tpu``)."""
    return jax.default_backend()


def has_accelerator() -> bool:
    """True iff the selected backend is a real accelerator — the signal the
    tri-state ``interpret=None`` auto-mode keys off."""
    return default_backend() in ("gpu", "tpu")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and nothing here overrides it; otherwise the cache
    lives at ``<checkout>/.jax_cache``. The path is part of every cache
    key, so it is fixed: never derived from a temp name, a pid or the
    clock. Launchers call this from ``main()``, never at import."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# KernelConfig: the single source of kernel execution settings
# ---------------------------------------------------------------------------

class KernelConfig(NamedTuple):
    """Process-wide Pallas execution settings.

    - ``interpret``: tri-state. ``True`` = run kernel bodies with jax ops
      (the CPU validation mode), ``False`` = compile for the device,
      ``None`` = auto (compiled iff `has_accelerator()`).
    - ``block_rows``: default bank-tile rows for streamed kernels
      (nn_search n_block, lazy_apply row_block, ...).
    - ``block_ids``: default id-block for gather-style kernels.
    - ``vmem_limit_bytes``: per-core VMEM budget the tile-sizing helpers
      fit against.
    """

    interpret: Optional[bool] = None
    block_rows: int = DEFAULT_BLOCK_ROWS
    block_ids: int = DEFAULT_BLOCK_IDS
    vmem_limit_bytes: int = DEFAULT_VMEM_BYTES

    def resolved_interpret(self) -> bool:
        if self.interpret is None:
            return not has_accelerator()
        return bool(self.interpret)


_lock = threading.Lock()
_config: Optional[KernelConfig] = None


def _parse_tristate(s: str) -> Optional[bool]:
    s = s.strip().lower()
    if s in ("", "auto", "none"):
        return None
    if s in ("1", "true", "yes", "on", "interpret"):
        return True
    if s in ("0", "false", "no", "off", "compiled"):
        return False
    raise ValueError(f"cannot parse interpret setting {s!r} "
                     "(want auto | true | false)")


def _from_env() -> KernelConfig:
    cfg = KernelConfig()
    if "REPRO_INTERPRET" in os.environ:
        cfg = cfg._replace(
            interpret=_parse_tristate(os.environ["REPRO_INTERPRET"]))
    if "REPRO_BLOCK_ROWS" in os.environ:
        cfg = cfg._replace(block_rows=int(os.environ["REPRO_BLOCK_ROWS"]))
    if "REPRO_BLOCK_IDS" in os.environ:
        cfg = cfg._replace(block_ids=int(os.environ["REPRO_BLOCK_IDS"]))
    if "REPRO_VMEM_MB" in os.environ:
        cfg = cfg._replace(
            vmem_limit_bytes=int(float(os.environ["REPRO_VMEM_MB"])
                                 * 2 ** 20))
    return cfg


def kernel_config() -> KernelConfig:
    """The process-wide config, resolving from the environment on first
    use. Cheap after the first call."""
    global _config
    if _config is None:
        with _lock:
            if _config is None:
                _config = _from_env()
    return _config


def set_kernel_config(config: Optional[KernelConfig] = None,
                      **overrides) -> KernelConfig:
    """Install the process-wide config (optionally overriding fields of the
    current one). Returns the previous config so tests can restore it.
    Note: jit caches key on the RESOLVED values (the public wrappers in
    `repro.kernels.ops` resolve before entering jit), so flipping the
    config mid-process recompiles rather than silently reusing stale
    programs."""
    global _config
    with _lock:
        prev = _config if _config is not None else _from_env()
        base = config if config is not None else prev
        _config = base._replace(**overrides) if overrides else base
    return prev


def reset_kernel_config() -> None:
    """Drop back to env-var resolution (tests)."""
    global _config
    with _lock:
        _config = None


def resolve_interpret(value: Optional[bool] = None) -> bool:
    """The per-call resolution every kernel entry point uses: an explicit
    ``True``/``False`` wins; ``None`` defers to the process config."""
    if value is None:
        return kernel_config().resolved_interpret()
    return bool(value)


# ---------------------------------------------------------------------------
# VMEM-aware tile sizing
# ---------------------------------------------------------------------------

def _legal_rows(rows: int) -> int:
    """Floor to a legal tile row count: multiples of 128 above 128 (the
    TPU lane tile), pow2 below, never under 8 (the sublane tile)."""
    rows = max(8, rows)
    if rows >= 128:
        return (rows // 128) * 128
    return 1 << (rows.bit_length() - 1)


def fit_block_rows(dim: int, *, want: Optional[int] = None,
                   n_arrays: int = 2, dtype_bytes: int = 4,
                   fixed_bytes: int = 0,
                   budget: Optional[int] = None) -> int:
    """Largest legal row-tile <= ``want`` whose working set fits the VMEM
    budget: ``n_arrays`` double-buffered (rows, dim) streams plus
    ``fixed_bytes`` of batch-shaped scratch."""
    cfg = kernel_config()
    want = cfg.block_rows if want is None else want
    budget = cfg.vmem_limit_bytes if budget is None else budget
    per_row = max(1, dim) * dtype_bytes * n_arrays * 2   # double-buffered
    avail = max(0, budget - fixed_bytes)
    return _legal_rows(min(want, max(8, avail // per_row)))


def fused_lookup_block(batch: int, dim: int, *, want: Optional[int] = None,
                       budget: Optional[int] = None) -> int:
    """Bank-tile rows for the int8 fused lookup
    (``repro.kernels.kb_fused_lookup``): it holds an (n_block, B) one-hot,
    a resident (B, D) fp32 output, and ~10 streamed (n_block, D) tiles in
    VMEM at once; every row is padded to whole 128-lane vregs. For B > 4k
    ids the old fixed n_block=512 overflows a 16 MiB core — this shrinks
    the tile until the working set fits (and the batch-shaped scratch
    alone exceeding the budget raises rather than producing an illegal
    tile). The fp32 lookup has no tile: it gathers its rows by id, so its
    work follows B, not the bank's N rows."""
    cfg = kernel_config()
    want = cfg.block_ids if want is None else want
    budget = cfg.vmem_limit_bytes if budget is None else budget
    b = max(8, -(-batch // 8) * 8)                  # padded batch
    d = -(-max(1, dim) // 128) * 128                # lane-padded row width
    # the resident (B, D) output (2 buffers), the (1, B) id row (8
    # sublanes, 2 buffers), and one (512, D) gather product in flight
    fixed = 2 * b * d * 4 + 2 * 8 * b * 4 + 512 * d * 4
    # per bank row: one one-hot row (B floats, double-buffered compute)
    # + ~10 streamed (row, D) tiles (5 in + 5 out), double-buffered
    per_row = 2 * b * 4 + 10 * d * 4 * 2
    avail = budget - fixed
    if avail < per_row * 8:
        raise ValueError(
            f"fused-lookup batch {batch} x dim {dim} cannot fit the "
            f"{budget >> 20} MiB VMEM budget at any legal tile; split the "
            "batch or raise the budget (REPRO_VMEM_MB)")
    return _legal_rows(min(want, avail // per_row))


# ---------------------------------------------------------------------------
# CLI plumbing shared by serve.py / train.py
# ---------------------------------------------------------------------------

def add_device_args(ap) -> None:
    """The device/runtime flag set, one definition for every launcher."""
    ap.add_argument("--platform", choices=("cpu", "gpu", "tpu"),
                    default=None,
                    help="pin the jax platform (default: jax's choice)")
    ap.add_argument("--x64", action="store_true",
                    help="enable 64-bit jax (host analysis only)")
    ap.add_argument("--interpret", choices=("auto", "true", "false"),
                    default=None,
                    help="Pallas kernel mode: auto (compiled iff an "
                         "accelerator is present), true (interpret "
                         "everywhere), false (force compiled)")
    ap.add_argument("--block-rows", type=int, default=None,
                    help="bank-tile rows for streamed kernels "
                         f"(default {DEFAULT_BLOCK_ROWS})")
    ap.add_argument("--block-ids", type=int, default=None,
                    help="id-block for gather-style kernels "
                         f"(default {DEFAULT_BLOCK_IDS})")
    ap.add_argument("--vmem-mb", type=float, default=None,
                    help="per-core VMEM budget for tile sizing "
                         f"(default {DEFAULT_VMEM_BYTES >> 20})")


def apply_device_args(args) -> KernelConfig:
    """Resolve the flags from `add_device_args` into the process config.
    Platform/x64 apply immediately; kernel settings install via
    `set_kernel_config` and are returned."""
    if getattr(args, "platform", None):
        set_platform(args.platform)
    if getattr(args, "x64", False):
        jax_enable_x64(True)
    overrides = {}
    if getattr(args, "interpret", None) is not None:
        overrides["interpret"] = _parse_tristate(args.interpret)
    if getattr(args, "block_rows", None) is not None:
        overrides["block_rows"] = int(args.block_rows)
    if getattr(args, "block_ids", None) is not None:
        overrides["block_ids"] = int(args.block_ids)
    if getattr(args, "vmem_mb", None) is not None:
        overrides["vmem_limit_bytes"] = int(args.vmem_mb * 2 ** 20)
    if overrides:
        set_kernel_config(kernel_config(), **overrides)
    return kernel_config()
