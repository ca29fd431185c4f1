"""EngineConfig — the one knob bundle for MNF compute (DESIGN.md §3).

Every execution-path parameter that used to be scattered across
``mnf_linear`` / ``tap_event_conv2d`` / ``event_matmul`` / ``fire_and_encode``
call sites (tile shapes, event capacity, fire threshold, interpret mode,
backend choice) lives here, so layers pass one object down the stack and
new backends (sharded, quantized) extend the config instead of every
signature in the repo.
"""
from __future__ import annotations

import dataclasses

import jax

__all__ = ["BACKENDS", "RECURRENT_BLK_K", "EngineConfig"]

#: Default K-block width of the fire-gated recurrent decode (DESIGN.md §13).
#: A per-token drive is a single row (blk_m == 1), so the only useful event
#: granularity is narrow K blocks over the channel axis: 16 channels per
#: block gives head_dim-64 wkv6 four independently skippable state
#: row-blocks (and a Mamba d_inner of 1536 ninety-six) while staying a
#: whole sublane-multiple payload.  ``for_recurrent`` clamps to
#: min(cfg.blk_k, RECURRENT_BLK_K, D); pass a smaller ``blk_k`` to sweep
#: finer granularities.
RECURRENT_BLK_K = 16

#: Execution backends, in "fidelity order" (see DESIGN.md §4):
#:   dense  — jnp oracle (no event machinery; the correctness reference)
#:   scalar — paper-faithful Algorithm 1/2 scalar events (semantics/cost ref)
#:   block  — pure-jnp block-event dataflow (TPU encoding, XLA execution)
#:   pallas — the Pallas TPU kernels (interpret-mode on CPU)
BACKENDS = ("dense", "scalar", "block", "pallas")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """All knobs of the MNF event pipeline, consolidated.

    backend:    one of BACKENDS or "auto" (resolve per device: pallas on TPU,
                block elsewhere — DESIGN.md §4).
    blk_m:      event tile rows (row-group height of the block encoding).
    blk_k:      event tile K width (VMEM lane width on TPU).
    blk_n:      output tile width of the Pallas multiply kernel.
    capacity:   static event-list capacity per row group (None = lossless).
    threshold:  fire/encode threshold (0.0 == exact for ReLU networks).
    magnitude:  fire on |a| > threshold (LM generalization) vs a > threshold.
    signed:     explicit signed-event fire (DESIGN.md §13): same |a| > θ
                gate as ``magnitude`` but the emitted stream is *flagged*
                signed, so consumers that assume ReLU-family (non-negative)
                events reject it by name instead of silently mis-pooling.
                The recurrent decode path sets it (per-token deltas are
                two-sided); ``for_recurrent`` is the one adapter that
                turns it on.
    interpret:  run Pallas kernels in interpret mode; None = auto (interpret
                everywhere except real TPU devices).
    out_dtype:  accumulator/output dtype of the multiply phase.
    route:      boundary routing policy (DESIGN.md §11): "auto" routes by
                geometry alone (event path whenever one exists — the
                pre-adaptive behaviour), "adaptive" consults the crossover
                cost model (``costmodel.crossover``) against
                ``occupancy_hint``, and "dense" / "event" / "strip" /
                "pixel" / "window" force a route (tests, benches).  Every
                value is a trace-time constant, so routing is static per
                compiled boundary.
    occupancy_hint: expected occupancy of incoming streams in [0, 1]
                (None = assume 1.0).  A *static* planning value — adaptive
                routing deliberately never reads the traced
                ``EventStream.occupancy()`` (jit-compiled boundaries must
                not route on data).
    int8_events: fire emits int8 event values (DESIGN.md §12): the fired
                map is requantized per layer (symmetric, dynamic
                calibration) and the stream carries the ``QParams``;
                consumers dequantize at tile load, so accumulators stay
                f32 and the chain matches its fake-quant round-trip twin
                bitwise within a backend.
    int8_bits:  quantization width (8 = int8; kept a knob so narrower
                event payloads can be explored without a new config).
    """

    backend: str = "auto"
    blk_m: int = 8
    blk_k: int = 128
    blk_n: int = 128
    capacity: int | None = None
    threshold: float = 0.0
    magnitude: bool = False
    signed: bool = False
    interpret: bool | None = None
    out_dtype: str = "float32"
    route: str = "auto"
    occupancy_hint: float | None = None
    int8_events: bool = False
    int8_bits: int = 8

    # NOTE: backend names beyond BACKENDS are allowed — the registry is open
    # (custom backends register at runtime); unknown names fail at dispatch
    # with the list of what IS registered.

    # -- resolution ---------------------------------------------------------

    def resolve_backend(self) -> str:
        """Concrete backend name ("auto" -> per-device policy)."""
        if self.backend != "auto":
            return self.backend
        return "pallas" if jax.default_backend() == "tpu" else "block"

    def resolve_interpret(self) -> bool:
        """Pallas interpret mode (None -> interpret off TPU only)."""
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() != "tpu"

    def resolved(self) -> "EngineConfig":
        """Pin backend + interpret to their per-device values."""
        return dataclasses.replace(self, backend=self.resolve_backend(),
                                   interpret=self.resolve_interpret())

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)

    # -- adapters -----------------------------------------------------------

    @classmethod
    def from_mnf(cls, mnf) -> "EngineConfig":
        """Build from a ``configs.base.MNFConfig`` (the model-stack knobs)."""
        return cls(backend="pallas" if mnf.use_pallas else "block",
                   blk_m=mnf.blk_m, blk_k=mnf.blk_k,
                   threshold=mnf.threshold, magnitude=mnf.magnitude)

    def for_recurrent(self, k: int) -> "EngineConfig":
        """The config a fire-gated recurrent decode step runs under.

        A per-token drive is one row per (batch × head) — ``blk_m`` is
        forced to 1 — and the gating granularity is narrow K blocks over
        the channel axis (``RECURRENT_BLK_K``, further clamped by the
        drive width and any explicitly smaller ``blk_k``).  ``signed`` is
        turned on: recurrent deltas are two-sided, and the emitted stream
        must say so (DESIGN.md §13).
        """
        return dataclasses.replace(
            self, blk_m=1,
            blk_k=min(self.blk_k, RECURRENT_BLK_K, max(k, 1)),
            signed=True)

    def for_width(self, m: int, k: int) -> "EngineConfig":
        """Clamp tile sizes to an (M, K) operand (small CPU test shapes)."""
        return dataclasses.replace(self, blk_m=min(self.blk_m, max(m, 1)),
                                   blk_k=min(self.blk_k, max(k, 1)))

    def for_conv(self, ci: int, *, width: int | None = None,
                 k: int | None = None, stride: int = 1, padding: int = 0,
                 co: int | None = None,
                 strips: bool | None = None) -> "EngineConfig":
        """Clamp the K tile to a conv's input-channel depth; optionally pick
        the event-row granularity (strip vs pixel tiling — DESIGN.md §6).

        Conv taps contract over CI, so a ``blk_k`` wider than CI would only
        pad; every conv backend applies this one clamp
        (``core.events.channel_blk_k``: CI itself when it fits, else a
        width that divides CI where one exists).

        With ``width`` and ``k`` given, also resolves ``blk_m``: STRIP_W
        (8-pixel row strips — the fused-tap kernel's granularity) when the
        layer is strip-eligible (stride in ``core.events.STRIP_STRIDES``,
        i.e. unit-stride and stride-2 downsampling convs both qualify), 1
        (pixel) otherwise.  ``strips=True`` *requires* strip tiling: a
        stride/width combo that would silently degrade to pixel granularity
        raises ``ValueError`` naming the failing rule instead.
        ``strips=False`` forces pixel tiling.
        """
        from repro.core.events import (STRIP_W, channel_blk_k,
                                       strip_ineligible_reason)

        cfg = dataclasses.replace(self, blk_k=channel_blk_k(ci, self.blk_k))
        if width is None and k is None and strips is None:
            return cfg
        if strips is False:
            return dataclasses.replace(cfg, blk_m=1)
        if width is None or k is None:
            raise ValueError(
                "for_conv strip selection needs the conv geometry: "
                "width= and k= (got width=%r, k=%r)" % (width, k))
        reason = strip_ineligible_reason(width, k, stride, padding, co)
        if strips and reason is not None:
            raise ValueError(
                f"strip tiling explicitly requested but the conv geometry "
                f"(width={width}, k={k}, stride={stride}, padding={padding}) "
                f"would silently degrade to pixel granularity: {reason}")
        return dataclasses.replace(cfg,
                                   blk_m=1 if reason is not None else STRIP_W)

    def for_pool(self, c: int, *, width: int | None = None,
                 k: int | None = None, stride: int = 1, padding: int = 0,
                 co: int | None = None) -> "EngineConfig":
        """Resolve the config an event-native max-pool emits under.

        ``c`` is the pooled channel depth (pooling preserves channels, so
        the K clamp mirrors :meth:`for_conv`).  ``blk_m`` becomes the
        granularity of the **emitted** pooled stream, chosen from its
        consumer: pass the consuming conv's geometry (``width`` = pooled
        map width, plus ``k``/``stride``/``padding``/``co``) to upgrade to
        strip tiling when that conv can ride the fused-tap kernel; with no
        consumer geometry the pooled stream stays pixel-granular
        (DESIGN.md §7).
        """
        from repro.core.events import (STRIP_W, channel_blk_k,
                                       strip_ineligible_reason)

        cfg = dataclasses.replace(self, blk_k=channel_blk_k(c, self.blk_k))
        if width is None or k is None:
            return dataclasses.replace(cfg, blk_m=1)
        reason = strip_ineligible_reason(width, k, stride, padding, co)
        return dataclasses.replace(cfg,
                                   blk_m=1 if reason is not None else STRIP_W)
