"""repro.numerics — the single public configuration spine.

The paper's result is a *recipe*: split count, scale bits, kept terms,
accumulation order.  Before this module the recipe was smeared across
string policy names, an 11-variable ``REPRO_*`` env namespace,
``DispatchConfig.override()``, and per-call kwargs — with a documented
footgun that config changes silently did not retrigger tracing.  This
module replaces all of that with one frozen, hashable
:class:`NumericsConfig` and one precedence rule:

    call-site kwarg  >  innermost ``with repro.numerics.use(...)``
    context          >  process env defaults (parsed once, on first use,
    through the typed registry below)

Three layers live here:

* **Env registry** (:data:`ENV_VARS`) — the canonical list of every
  ``REPRO_*`` variable: name, type, default, docstring.  All environment
  reads in ``src/`` go through :func:`env_value`; a tier-1 test greps the
  tree and fails on any read outside this module, so the sprawl can never
  regrow.  Parsing is typed and total: empty values mean "unset", garbage
  values warn and fall back to the default (``REPRO_FORCE_PALLAS=0`` is
  off, ``REPRO_PALLAS_MIN_DIM=`` is the default — the old truthy-parse
  asymmetries are gone).

* **Config + context** — :func:`active` returns the innermost
  :func:`use` context on this thread, else the env-default config.
  Contexts nest and are thread-local (a worker thread starts from the env
  defaults, not from another thread's context).

* **Trace correctness** — the active config travels as part of the jit
  cache key: every distinct config is interned to a *config epoch*, and
  :func:`use` installs the epoch in JAX's trace context (via
  ``jax.experimental.xla_metadata``).
  Entering or exiting a context therefore deterministically re-lowers
  previously-jitted shapes instead of silently reusing a stale dispatch
  decision; re-entering a config that was already traced reuses its
  cached lowering.

The public verb layer — :func:`matmul`, :func:`einsum`,
:func:`attention` (re-exported as ``repro.matmul`` etc.) — resolves the
policy and kernel knobs through this config, so callers never import
``repro.kernels.*`` or ``repro.core.policy`` directly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import warnings
from dataclasses import dataclass, replace

__all__ = [
    "ENV_VARS", "EnvVar", "NumericsConfig", "active", "use", "env_value",
    "reload_env_defaults", "describe_env", "env_table", "config_epoch",
    "matmul", "einsum", "attention",
]

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


# --------------------------------------------------------------- registry

@dataclass(frozen=True)
class EnvVar:
    """One registered ``REPRO_*`` environment variable."""
    name: str
    kind: str                  # "bool" | "int" | "str" | "path"
    default: object
    doc: str
    field: str | None = None   # NumericsConfig field it feeds (None = raw)
    invert: bool = False       # bool vars that *unset* their field


def _parse_bool(raw: str | None, default):
    if raw is None:
        return default
    t = raw.strip().lower()
    if t == "":
        return default
    if t in _TRUE:
        return True
    if t in _FALSE:
        return False
    warnings.warn(f"unrecognized boolean value {raw!r}; using default "
                  f"{default!r}", stacklevel=3)
    return default


def _parse_int(raw: str | None, default):
    if raw is None or raw.strip() == "":
        return default
    try:
        return int(raw.strip())
    except ValueError:
        warnings.warn(f"unrecognized integer value {raw!r}; using default "
                      f"{default!r}", stacklevel=3)
        return default


def _parse_str(raw: str | None, default):
    if raw is None or raw.strip() == "":
        return default
    return raw.strip()


_PARSERS = {"bool": _parse_bool, "int": _parse_int, "str": _parse_str,
            "path": _parse_str}

_DEFAULT_TUNE_CACHE = os.path.join(
    os.path.expanduser("~"), ".cache", "repro", "tcec_autotune.json")

# The canonical REPRO_* namespace.  Order is the documentation order.
ENV_VARS: dict[str, EnvVar] = {v.name: v for v in [
    EnvVar("REPRO_POLICY", "str", "fp32",
           "Default GEMM precision policy for the repro.matmul / "
           "repro.einsum / repro.attention verbs (call-site kwargs and "
           "model configs still win).", field="policy"),
    EnvVar("REPRO_DISABLE_PALLAS", "bool", False,
           "Escape hatch: route every contraction to the XLA "
           "term-expansion fallback.", field="enabled", invert=True),
    EnvVar("REPRO_FORCE_PALLAS", "bool", False,
           "Dispatch to the fused kernels even off-TPU (interpret mode — "
           "tests, CPU verification).", field="force"),
    EnvVar("REPRO_PALLAS_MIN_DIM", "int", 128,
           "Smallest M/N/K (GEMM) or S/T (attention) worth dispatching: "
           "tiny problems lose more to 128-padding than fusion wins.",
           field="min_dim"),
    EnvVar("REPRO_FUSE_EPILOGUE", "bool", False,
           "Fold bias + activation into the GEMM kernel's scaled epilogue "
           "(models.layers.fused_linear).", field="fuse_epilogue"),
    EnvVar("REPRO_DISABLE_FLASH_ATTN", "bool", False,
           "Granular hatch: keep GEMM dispatch but not the fused "
           "flash-attention kernel.", field="flash_attention", invert=True),
    EnvVar("REPRO_DISABLE_PAGED_ATTN", "bool", False,
           "Granular hatch: keep the rest but not the paged "
           "decode-attention kernel (restores exact dense parity).",
           field="paged_attention", invert=True),
    EnvVar("REPRO_SHARD_MAP", "bool", True,
           "Under an installed GSPMD mesh, wrap kernel dispatch in "
           "shard_map (per-device shards; kernels/shmap.py).  0 declines "
           "every dispatch under a mesh to the XLA fallback.",
           field="shard_map"),
    EnvVar("REPRO_TUNE", "bool", False,
           "Force autotuner measurement even off-TPU.", field="tune"),
    EnvVar("REPRO_TUNE_DISABLE", "bool", False,
           "Never measure; heuristic blocks only (wins over REPRO_TUNE).",
           field="tune"),
    EnvVar("REPRO_TUNE_CACHE", "path", _DEFAULT_TUNE_CACHE,
           "Autotuner cache file path.", field="tune_cache"),
    EnvVar("REPRO_GUARD", "bool", True,
           "Guarded dispatch: a fused-kernel failure falls back to the "
           "XLA term-expansion path and quarantines that (backend, kernel, "
           "shape-bucket) key for a cooldown (kernels/guard.py).  0 lets "
           "kernel errors propagate (debugging).", field="guard"),
    EnvVar("REPRO_PREFIX_CACHE", "bool", False,
           "Serving engine: share full prompt pages across requests via "
           "the copy-on-write prefix cache (serving/prefix_cache.py) — "
           "a cached prefix skips its recompute and only the novel tail "
           "prefills.", field="prefix_cache"),
    EnvVar("REPRO_CHUNKED_PREFILL", "int", 0,
           "Serving engine: prefill prompts in chunks of this many tokens "
           "(rounded up to the page size), interleaved with decode steps "
           "so long prompts stop head-of-line-blocking admissions.  0 = "
           "monolithic single-shot prefill.", field="chunked_prefill"),
    EnvVar("REPRO_ASYNC_SCHED", "bool", False,
           "Serving engine: overlap host scheduling with the in-flight "
           "jitted decode step (dispatch one step ahead; block only at "
           "the consume point).  Token-identical to the synchronous "
           "default.", field="async_sched"),
    EnvVar("REPRO_MONITOR", "bool", False,
           "Numerics-health monitors: sampled per-contraction probes of "
           "the paper's underflow-risk indicators (correction-term "
           "underflow fractions, operand exponent range vs the policy's "
           "safe band), recorded into the repro.obs metrics registry "
           "(obs/numerics_health.py).  Off by default — probes add "
           "side computation per monitored contraction.",
           field="monitor"),
    EnvVar("REPRO_FAULTS", "str", "",
           "Fault-injection plan for chaos testing, e.g. "
           "'pool.alloc@0:1;decode.slow@every=4' (repro.faults; empty = "
           "no injection)."),
    EnvVar("REPRO_KEEP_BF16_DOTS", "bool", False,
           "Keep native bf16 dots in lowered HLO on CPU (compiled-artifact "
           "byte accounting for the dry-run; CPU execution may be "
           "unimplemented for some shapes).", field="keep_bf16_dots"),
    EnvVar("REPRO_DRYRUN_DEVICES", "int", 0,
           "Host-platform device count for launch.dryrun (0 = the 512-chip "
           "production world).  Read before JAX initializes."),
    EnvVar("REPRO_BENCH_OUT", "path", "experiments/bench",
           "Output directory for benchmark JSON artifacts."),
]}


def env_value(name: str, environ=None):
    """Typed read of a registered ``REPRO_*`` variable.

    The single chokepoint for environment access: empty values mean
    "unset", unparseable values warn and fall back to the registered
    default.  Unregistered names are a programming error.
    """
    var = ENV_VARS[name]
    raw = (environ if environ is not None else os.environ).get(name)
    return _PARSERS[var.kind](raw, var.default)


def describe_env() -> list[dict]:
    """Registry rows (name/type/default/doc) for docs and tooling."""
    return [{"name": v.name, "type": v.kind, "default": v.default,
             "doc": v.doc} for v in ENV_VARS.values()]


def env_table() -> str:
    """The registry as a markdown table (the docs' knob tables point here)."""
    rows = ["| variable | type | default | effect |",
            "|----------|------|---------|--------|"]
    for v in ENV_VARS.values():
        default = "" if v.default in ("", 0, False) else f"`{v.default}`"
        rows.append(f"| `{v.name}` | {v.kind} | {default} | {v.doc} |")
    return "\n".join(rows)


# ----------------------------------------------------------------- config

def _tuple_or_none(x, n, name):
    if x is None:
        return None
    t = tuple(int(v) for v in x)
    if len(t) != n:
        raise ValueError(f"{name} must have {n} entries, got {x!r}")
    return t


@dataclass(frozen=True)
class NumericsConfig:
    """The full recipe: policy selection, kernel dispatch, and tuning.

    Frozen and hashable — a value object that can key jit caches.  Field
    defaults are the env-variable defaults; see :data:`ENV_VARS` for the
    variable each field parses from.
    """
    # -- policy selection ---------------------------------------------
    policy: str = "fp32"            # default for the public verbs
    # -- kernel dispatch ----------------------------------------------
    enabled: bool = True            # False = XLA fallback wholesale
    force: bool = False             # dispatch even off-TPU (interpret)
    min_dim: int = 128              # smallest M/N/K (or S/T) to dispatch
    block: tuple | None = None      # (bm, bn, bk) GEMM autotuner override
    interpret: bool | None = None   # None = auto (interpret off-TPU)
    fuse_epilogue: bool = False     # models.layers.fused_linear hook
    flash_attention: bool = True    # fused attention kernel routing
    attn_block: tuple | None = None   # (bq, bk) attention override
    paged_attention: bool = True    # paged decode-attention routing
    paged_block: int | None = None  # pages-per-step override
    shard_map: bool = True          # mesh dispatch via kernels/shmap.py
    guard: bool = True              # circuit-breaker guarded dispatch
    # -- serving ------------------------------------------------------
    prefix_cache: bool = False      # COW prefix sharing (serving engine)
    chunked_prefill: int = 0        # prefill chunk tokens (0 = monolithic)
    async_sched: bool = False       # overlap host sched with device step
    # -- observability ------------------------------------------------
    monitor: bool = False           # numerics-health probes (repro.obs)
    # -- autotuning ---------------------------------------------------
    tune: str = "auto"              # "auto" | "force" | "off"
    tune_cache: str = _DEFAULT_TUNE_CACHE
    # -- numerics environment -----------------------------------------
    keep_bf16_dots: bool = False    # keep bf16 dots in CPU-lowered HLO

    def __post_init__(self):
        object.__setattr__(self, "block",
                           _tuple_or_none(self.block, 3, "block"))
        object.__setattr__(self, "attn_block",
                           _tuple_or_none(self.attn_block, 2, "attn_block"))
        if self.tune not in ("auto", "force", "off"):
            raise ValueError(f"tune must be auto|force|off, got {self.tune!r}")
        # fail at the use()/construction site, not as a bare KeyError at
        # the first verb call much later
        from repro.core.policy import POLICIES
        if self.policy not in POLICIES:
            raise ValueError(f"unknown policy {self.policy!r}; "
                             f"known: {sorted(POLICIES)}")

    def replace(self, **overrides) -> "NumericsConfig":
        return replace(self, **_canon_overrides(overrides))

    @staticmethod
    def from_env(environ=None) -> "NumericsConfig":
        """Parse the registry into a config (the process-default recipe)."""
        tune = "auto"
        if env_value("REPRO_TUNE", environ):
            tune = "force"
        if env_value("REPRO_TUNE_DISABLE", environ):
            tune = "off"                       # disable wins over force
        from repro.core.policy import POLICIES
        policy = env_value("REPRO_POLICY", environ)
        if policy not in POLICIES:
            warnings.warn(f"REPRO_POLICY={policy!r} is not a registered "
                          f"policy; using {ENV_VARS['REPRO_POLICY'].default!r}")
            policy = ENV_VARS["REPRO_POLICY"].default
        return NumericsConfig(
            policy=policy,
            enabled=not env_value("REPRO_DISABLE_PALLAS", environ),
            force=env_value("REPRO_FORCE_PALLAS", environ),
            min_dim=env_value("REPRO_PALLAS_MIN_DIM", environ),
            fuse_epilogue=env_value("REPRO_FUSE_EPILOGUE", environ),
            flash_attention=not env_value("REPRO_DISABLE_FLASH_ATTN",
                                          environ),
            paged_attention=not env_value("REPRO_DISABLE_PAGED_ATTN",
                                          environ),
            shard_map=env_value("REPRO_SHARD_MAP", environ),
            guard=env_value("REPRO_GUARD", environ),
            prefix_cache=env_value("REPRO_PREFIX_CACHE", environ),
            chunked_prefill=env_value("REPRO_CHUNKED_PREFILL", environ),
            async_sched=env_value("REPRO_ASYNC_SCHED", environ),
            monitor=env_value("REPRO_MONITOR", environ),
            tune=tune,
            tune_cache=env_value("REPRO_TUNE_CACHE", environ),
            keep_bf16_dots=env_value("REPRO_KEEP_BF16_DOTS", environ),
        )


_CONFIG_FIELDS = frozenset(f.name for f in dataclasses.fields(NumericsConfig))


def _canon_overrides(overrides: dict) -> dict:
    unknown = set(overrides) - _CONFIG_FIELDS
    if unknown:
        raise TypeError(f"unknown numerics option(s): {sorted(unknown)}; "
                        f"valid fields: {sorted(_CONFIG_FIELDS)}")
    out = dict(overrides)
    if "policy" in out and out["policy"] is not None \
            and not isinstance(out["policy"], str):
        out["policy"] = out["policy"].name     # PrecisionPolicy instance
    return out


# -------------------------------------------------- context + env default

_tls = threading.local()
_env_default_lock = threading.Lock()
_ENV_DEFAULT: NumericsConfig | None = None


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


def _env_default() -> NumericsConfig:
    global _ENV_DEFAULT
    if _ENV_DEFAULT is None:
        with _env_default_lock:
            if _ENV_DEFAULT is None:
                _ENV_DEFAULT = NumericsConfig.from_env()
    return _ENV_DEFAULT


def reload_env_defaults() -> NumericsConfig:
    """Re-parse the env into the process-default config (tests; long-lived
    processes toggling hatches).  If the default actually changed, jit
    caches are cleared — ambient traces would otherwise keep the stale
    recipe (the same staleness :func:`use` solves with config epochs)."""
    global _ENV_DEFAULT
    with _env_default_lock:
        old = _ENV_DEFAULT
        _ENV_DEFAULT = NumericsConfig.from_env()
        changed = old is not None and old != _ENV_DEFAULT
    if changed:
        import jax
        jax.clear_caches()
    return _ENV_DEFAULT


def active() -> NumericsConfig:
    """The innermost context on this thread, else the env defaults."""
    stack = _stack()
    return stack[-1] if stack else _env_default()


# ------------------------------------------------------------ config epoch
#
# Each distinct config is interned to a small integer (its *epoch*).  use()
# installs the epoch in JAX's trace context, so every jit cache downstream
# keys on it: entering a context re-lowers previously-jitted shapes under
# the new recipe, and re-entering an already-seen config hits the cache.

_epoch_lock = threading.Lock()
_EPOCH_IDS: dict[NumericsConfig, int] = {}


def config_epoch(cfg: NumericsConfig | None = None) -> int:
    """The interned epoch id of ``cfg`` (default: the active config).
    Epoch 0 is the env-default config; distinct configs get distinct ids."""
    cfg = cfg if cfg is not None else active()
    if cfg == _env_default():
        return 0
    with _epoch_lock:
        eid = _EPOCH_IDS.get(cfg)
        if eid is None:
            eid = len(_EPOCH_IDS) + 1
            _EPOCH_IDS[cfg] = eid
    return eid


def _epoch_scope(cfg: NumericsConfig):
    """Context manager keying JAX trace caches on ``cfg``'s epoch.

    Uses ``jax.experimental.xla_metadata`` (part of jax's trace context,
    so tracing caches and executable caches both key on it).

    Epoch 0 (the env-default config) is tagged too: a restore-to-default
    context nested inside a non-default one must *replace* the enclosing
    epoch, or its traces would be keyed (and later cache-hit) under the
    outer config.
    """
    from jax.experimental.xla_metadata import set_xla_metadata
    return set_xla_metadata(repro_numerics_epoch=str(config_epoch(cfg)))


@contextlib.contextmanager
def _scoped(cfg: NumericsConfig):
    """Plain thread-local push, no epoch tag.

    Used for call-site kwargs (the verbs) where the override is a constant
    of the caller's own code: re-traces re-execute the verb body, so the
    jit key needs no extra state."""
    stack = _stack()
    stack.append(cfg)
    try:
        yield cfg
    finally:
        stack.pop()


@contextlib.contextmanager
def use(config: NumericsConfig | None = None, **overrides):
    """Scoped numerics config: ``with repro.numerics.use(policy="tcec_bf16x6",
    force=True): ...``.

    Pass field overrides (applied on the *current* active config — contexts
    nest), or a full :class:`NumericsConfig`, or both (overrides applied on
    the instance).  The context is thread-local and trace-correct: jit
    caches key on the config's epoch, so previously-traced shapes re-lower
    under the new recipe instead of reusing a stale dispatch decision.
    """
    if config is not None:
        if not isinstance(config, NumericsConfig):
            raise TypeError(f"expected NumericsConfig, got {type(config)}")
        cfg = config.replace(**overrides) if overrides else config
    else:
        cfg = active().replace(**overrides)
    with _scoped(cfg), _epoch_scope(cfg):
        yield cfg


def _call_config(overrides: dict) -> NumericsConfig:
    """Call-site kwarg resolution: innermost context + per-call overrides."""
    cfg = active()
    return cfg.replace(**overrides) if overrides else cfg


# ------------------------------------------------------------- verb layer
#
# The public entry points (re-exported as repro.matmul / repro.einsum /
# repro.attention).  Heavy imports are deferred so `import repro` stays
# cheap and this module never participates in an import cycle.

def matmul(a, b, *, policy=None, **overrides):
    """Policy-routed matmul: ``(M, K) @ (K, N)`` or batched ``(B, M, K) @
    (B, K, N)``, f32 accumulation, differentiable (policy-preserving
    backward), dispatched to the fused Pallas kernel when eligible.

    ``policy`` defaults to the active config's (context or ``REPRO_POLICY``
    env default).  Extra kwargs are per-call config overrides — the highest
    precedence level: ``repro.matmul(a, b, policy="tcec_bf16x6",
    force=True, interpret=True)``.
    """
    from repro.core.policy import get_policy, policy_bmm, policy_mm
    cfg = _call_config(overrides)
    pol = get_policy(policy if policy is not None else cfg.policy)
    with _scoped(cfg):
        if getattr(a, "ndim", 2) == 3:
            return policy_bmm(a, b, pol)
        return policy_mm(a, b, pol)


def einsum(subscripts: str, a, b, *, policy=None, **overrides):
    """Policy-routed binary einsum (any two-operand contraction with no
    repeated indices — the framework's single GEMM chokepoint).  Same
    precedence rules as :func:`matmul`."""
    from repro.core.policy import get_policy, pdot
    cfg = _call_config(overrides)
    pol = get_policy(policy if policy is not None else cfg.policy)
    with _scoped(cfg):
        return pdot(subscripts, a, b, pol)


def attention(q, k, v, *, policy=None, q_pos=None, k_pos=None,
              causal: bool = True, window=0, softcap: float | None = None,
              **overrides):
    """Policy-routed scaled-dot-product attention.

    q ``(B, S, H, hd)``, k/v ``(B, T, Hkv, hd[v])`` with GQA by head
    grouping (``H % Hkv == 0``).  Routes to the fused TCEC flash-attention
    kernel when the active config allows, with the pdot composition as
    fallback and as the backward (recompute) path.  Positions default to
    ``arange``; same precedence rules as :func:`matmul`.
    """
    import jax.numpy as jnp
    from repro.core.policy import get_policy
    from repro.models import layers as L
    cfg = _call_config(overrides)
    pol = get_policy(policy if policy is not None else cfg.policy)
    B, S = q.shape[0], q.shape[1]
    T = k.shape[1]
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if k_pos is None:
        k_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    class _Shim:
        mix_policy = pol
        attn_softcap = softcap

    with _scoped(cfg):
        return L.sdpa(q, k, v, _Shim, q_pos, k_pos, causal, window)


# ------------------------------------------------------------ CLI support

def parse_override_args(pairs) -> dict:
    """Parse CLI ``key=value`` pairs into :func:`use` overrides.

    Used by the launch binaries (``--numerics force=1 --numerics
    min_dim=0``).  Values are coerced by the target field's type: bools
    accept the registry's truthy/falsy spellings, ``none`` clears an
    optional field, tuples parse from comma-separated ints.
    """
    out = {}
    for pair in pairs or []:
        key, sep, raw = pair.partition("=")
        key = key.strip()
        if not sep or key not in _CONFIG_FIELDS:
            raise ValueError(
                f"bad --numerics override {pair!r}; expected key=value with "
                f"key in {sorted(_CONFIG_FIELDS)}")
        raw = raw.strip()
        if raw.lower() in ("none", ""):
            # only the genuinely-optional fields may be cleared
            if key not in ("block", "attn_block", "paged_block", "interpret"):
                raise ValueError(f"{key} cannot be set to none ({pair!r})")
            out[key] = None
        elif key in ("block", "attn_block"):
            out[key] = tuple(int(v) for v in raw.split(","))
        elif key in ("policy", "tune", "tune_cache"):
            out[key] = raw
        elif key in ("min_dim", "paged_block", "chunked_prefill"):
            out[key] = int(raw)
        elif raw.lower() in _TRUE:             # the bool fields
            out[key] = True
        elif raw.lower() in _FALSE:
            out[key] = False
        else:
            raise ValueError(f"bad boolean in override {pair!r}")
    return out


def add_cli_overrides(parser) -> None:
    """Register the shared ``--numerics KEY=VALUE`` argparse flag."""
    parser.add_argument(
        "--numerics", action="append", default=[], metavar="KEY=VALUE",
        help="numerics config override (repeatable), e.g. --numerics "
             "policy=tcec_bf16x6 --numerics enabled=false; keys are "
             "repro.numerics.NumericsConfig fields")


def cli_context(args):
    """The ``use(...)`` context for parsed CLI args (no-op when empty)."""
    return use(**parse_override_args(getattr(args, "numerics", None)))


# ----------------------------------------------------------- deprecations

def _deprecated(old: str, new: str):
    warnings.warn(f"{old} is deprecated; use {new}", DeprecationWarning,
                  stacklevel=3)


def _legacy_flag(name: str) -> bool:
    """Exact semantics of the retired ``dispatch.env_flag`` for its
    deprecation shim: truthy parse of ANY variable (registered or not),
    unset/empty/falsy spellings -> False, anything else -> True.  Lives
    here so the only environment reads in src/ stay in this module."""
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "no", "off")
