"""Public megastep API: backend dispatch + the wrapper-stack adapter.

`env_megastep` is the raw row-level op (pallas | pallas_interpret | jnp, with
"auto" picking Pallas on TPU and the jnp reference elsewhere — the same
dispatch idiom as kernels/raster and kernels/attention).

`fused_step` is the high-level entry the pool and `Env.fused_step` use: it
takes the *batched autoreset state* exactly as `Vec(AutoReset(env))` carries
it, precomputes the auto-reset key chain and fresh reset states with the
identical `jax.random` call sequence `AutoReset.step` makes per step (so the
threefry stream is bit-exact against the vmap path), flattens the state to
rows, launches the kernel, and rebuilds the state pytree. Which parts of
the stack fuse how is read off the *declared* pipeline (core/pipeline.py):
every wrapper is a reconstructible transform carrying its fusion role, so
the planner (`_plan`) walks data instead of reverse-engineering wrapper
stacks with isinstance heuristics.

Pixel stacks (`FrameStack(ObsToPixels(core))` / `ObsToPixels(core)`, arcade
suite) fuse too, when the core spec's obs rows are its state rows
(`FusedSpec.obs_is_state`): the kernel advances the row-major game logic for
the whole K-step chunk, then the per-step frames are rasterised *outside*
the fused body — one batched `kernels.raster` call over all K·B scenes per
chunk — and the frame-stack ring and auto-reset select run in one pass
(`frame_stack`: the `_frame_stack_kernel` Pallas call of framestack.py, which
writes each stacked frame once, or its `lax.scan` reference under "jnp").
Everything stays on device; rendering work matches the vmap path exactly
(one stepped + one fresh frame per env per step).

Each sub-layer of the fused step runs under one `jax.named_scope`, which
reaches the compiled program's `op_name` metadata and so a profile's ops:
`cairl.reset` (the auto-reset key chain and fresh states), `cairl.layout`
(pytree <-> kernel rows, output casts), `cairl.megastep` (the kernel call),
`cairl.render` (scenes and the raster call) and `cairl.frame_stack` (the
frame ring and auto-reset select, the frame-stack kernel call). No name
contains another, so a substring match finds each alone.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.envstep.framestack import (frame_stack_pallas,
                                              frame_stack_ref)
from repro.kernels.envstep.megastep import megastep_pallas
from repro.kernels.envstep.ref import megastep_ref
from repro.kernels.envstep.specs import lookup


def env_megastep(step_rows, state, actions, fresh, fresh_obs, *,
                 max_steps: Optional[int] = None, backend: str = "auto",
                 batch_block: int = 128):
    """Row-level K-step fused op with backend dispatch.

    backend: "auto" (pallas on TPU, jnp elsewhere) | "pallas" |
    "pallas_interpret" | "jnp".
    """
    if backend == "auto":
        backend = "pallas" if on_tpu() else "jnp"
    if backend == "pallas":
        return megastep_pallas(step_rows, state, actions, fresh, fresh_obs,
                               max_steps=max_steps, batch_block=batch_block)
    if backend == "pallas_interpret":
        return megastep_pallas(step_rows, state, actions, fresh, fresh_obs,
                               max_steps=max_steps, batch_block=batch_block,
                               interpret=True)
    if backend == "jnp":
        return megastep_ref(step_rows, state, actions, fresh, fresh_obs,
                            max_steps=max_steps)
    raise ValueError(f"unknown backend {backend!r}")


def frame_stack(frames, pre, fresh, done, *, backend: str = "auto"):
    """The pixel pipeline's frame-stack ring with backend dispatch
    (framestack.py): (B, N, H, W) carried stack, (K, B, H, W) stepped and
    fresh frames, (K, B) done -> (new stack, obs, terminal obs).

    backend: "auto" (pallas on TPU, jnp elsewhere) | "pallas" |
    "pallas_interpret" | "jnp".
    """
    if backend == "auto":
        backend = "pallas" if on_tpu() else "jnp"
    if backend in ("pallas", "pallas_interpret"):
        return frame_stack_pallas(frames, pre, fresh, done,
                                  interpret=backend == "pallas_interpret")
    if backend == "jnp":
        return frame_stack_ref(frames, pre, fresh, done)
    raise ValueError(f"unknown backend {backend!r}")


def _plan(env):
    """Read the fusion plan off the stack's *declared* pipeline.

    Walks `pipeline.declared_pipeline(env)` — wrappers are reconstructible
    transforms carrying their fusion role (`Transform.fusion`) — and accepts
    the one shape the kernel models: `[TimeLimit] [ObsToPixels [FrameStack]]`
    over a base env. Returns (core_env_stack, num_stack, pixels) where
    `core_env_stack` is the TimeLimit(base)/bare-base sub-stack `lookup()`
    resolves, or (None, None, False) for anything the plan can't express
    (opaque wrappers, FrameStack without pixels, reordered transforms).
    """
    from repro.core import pipeline as P

    core, transforms = P.declared_pipeline(env)
    if core is None:
        return None, None, False
    stack = list(transforms)  # innermost-first; env is the outermost wrapper
    core_stack, num_stack, pixels = env, None, False
    if stack and stack[-1].fusion == P.FUSION_FRAME_STACK:
        num_stack = stack.pop().num_frames
        core_stack = core_stack.env
    if stack and stack[-1].fusion == P.FUSION_PIXELS:
        pixels = True
        stack.pop()
        core_stack = core_stack.env
    elif num_stack is not None:  # FrameStack over non-pixel obs: not modelled
        return None, None, False
    if stack and not (len(stack) == 1
                      and stack[0].fusion == P.FUSION_TIME_LIMIT):
        return None, None, False  # anything besides an inner TimeLimit
    return core_stack, num_stack, pixels


def _pixel_fusable(spec, core) -> bool:
    return bool(spec.obs_is_state) and hasattr(core.unwrapped, "scene")


def supports(env) -> bool:
    """True if `env` (base, TimeLimit(base), or a pixel wrapper stack over
    them) has a fused megastep execution path."""
    core, _, pixels = _plan(env)
    if core is None:
        return False
    found = lookup(core)
    if found is None:
        return False
    return _pixel_fusable(found[0], core) if pixels else True


def _render_obs_rows(core, spec, obs_rows, backend):
    """(K, O, B) obs rows -> (K·B, H, W) frames, step-major, one batched
    raster call.

    Valid because `spec.obs_is_state`: obs rows ARE state rows, so the
    capsule scene of every step is reconstructable on device from the
    kernel's per-step obs output — no per-step render inside the fused body.
    The frames are handed over flat: the consumer's (K, B) view is taken
    under its own scope, since XLA merges consecutive reshapes into one op
    that would otherwise carry both scopes.
    """
    from repro.kernels.raster import rasterize

    base = core.unwrapped
    k, _, b = obs_rows.shape
    with jax.named_scope("cairl.render"):
        states = jax.vmap(spec.unflatten)(obs_rows)
        segs, intens = jax.vmap(jax.vmap(base.scene))(states)
        h, w = base.frame_shape
        return rasterize(segs.reshape((k * b,) + segs.shape[2:]),
                         intens.reshape(k * b, -1), h, w, backend=backend)


def _mask_inactive(old_state, new_state, ts, active):
    """Masked-active lane gating (the serving/engine.py decode-slot pattern
    applied to env lanes): rows where `active` is False keep their pre-chunk
    state — including their AutoReset key chain, which must not advance for
    a lane that did not step — and report zero reward / obs and done=False.
    The kernel still computes every lane (SIMD lanes are paid for either
    way); the select is what makes slot recycling in the async pool unable
    to perturb neighbouring sessions."""
    from repro.core.env import Timestep

    act = jnp.asarray(active, bool)

    def lane(n, o):  # state leaves: (B, ...)
        return jnp.where(act.reshape(act.shape + (1,) * (n.ndim - 1)), n, o)

    def out(n):      # per-step output leaves: (K, B, ...)
        m = act.reshape((1,) + act.shape + (1,) * (n.ndim - 2))
        return jnp.where(m, n, jnp.zeros_like(n))

    sel_state = jax.tree.map(lane, new_state, old_state)
    info = {k: out(v) for k, v in ts.info.items()}
    return sel_state, Timestep(state=sel_state, obs=out(ts.obs),
                               reward=out(ts.reward), done=out(ts.done),
                               info=info)


def fused_step(env, state, actions, keys=None, num_steps: Optional[int] = None,
               *, backend: str = "auto", batch_block: int = 128, active=None):
    """Advance a batched `AutoReset(env)` state by `num_steps` fused steps.

    env     : the single-env stack the pool holds — `TimeLimit(base)` / base,
              optionally under `ObsToPixels` / `FrameStack(ObsToPixels(...))`
              (the arcade pixel pipeline).
    state   : `AutoResetState` with batched (B, ...) leaves — exactly the
              env_state `Vec(AutoReset(env))` carries.
    actions : (K, B) (discrete) or (K, B, 1) (continuous) action block.
    keys    : optional per-step key array; accepted for protocol symmetry
              with `Vec.step` and ignored — every fused env's dynamics are
              action-deterministic, and auto-reset randomness comes from the
              state's own key chain (like the vmap path).
    active  : optional (B,) bool lane mask (the async pool's masked chunk
              step): lanes where it is False keep their pre-chunk state and
              key chain and report zero reward / done=False. Default None
              steps every lane (lock-step).

    Returns `(new_state, ts)` where `ts` is a `Timestep` whose obs/reward/
    done/info leaves carry a leading (K, ...) step axis — the same stack
    `lax.scan` of `Vec(AutoReset(env)).step` would produce. `info` carries
    `terminal_obs` (pre-reset obs) and, when the stack has a TimeLimit,
    `truncated` (time-limit cut of a non-terminal state).
    """
    from repro.core.env import Timestep
    from repro.core.wrappers import (AutoResetState, FrameStackState,
                                     TimeLimitState)

    core, num_stack, pixels = _plan(env)
    found = lookup(core) if core is not None else None
    if found is None or (pixels and not _pixel_fusable(found[0], core)):
        raise NotImplementedError(
            f"no fused megastep spec for {type(env.unwrapped).__name__}; "
            "supported: CartPole, MountainCar, Pendulum, Acrobot, LightsOut, "
            "Pong, Breakout, FrozenLake, CliffWalk, Snake, Maze (bare or "
            "under a single TimeLimit, arcade also under ObsToPixels / "
            "FrameStack(ObsToPixels))")
    spec, max_steps = found

    acts = jnp.asarray(actions)
    if acts.ndim == 3 and acts.shape[-1] == 1:
        with jax.named_scope("cairl.layout"):
            acts = acts[..., 0]
    if acts.ndim != 2:
        raise ValueError(f"actions must be (K, B[, 1]); got {actions.shape}")
    k, b = acts.shape
    if num_steps is not None and num_steps != k:
        raise ValueError(f"num_steps={num_steps} != actions.shape[0]={k}")

    # Auto-reset key chain + fresh reset states, OUTSIDE the kernel: the same
    # per-step `split(state.key)` + `env.reset(reset_key)` AutoReset.step
    # performs, so the threefry stream matches the vmap path bit-for-bit.
    # Pixel wrappers pass the reset key through to the core untouched, so
    # resetting `core` here sees the exact stream the full-stack reset would;
    # the fresh *frames* are re-rendered from the fresh core obs rows below
    # instead of being materialised per stack slot.
    def reset_body(ks, _):
        pair = jax.vmap(jax.random.split)(ks)          # (B, 2, 2)
        fs, fo = jax.vmap(core.reset)(pair[:, 1])
        return pair[:, 0], (fs, fo)

    with jax.named_scope("cairl.reset"):
        final_keys, (fresh_states, fresh_obs) = jax.lax.scan(
            reset_body, state.key, None, length=k)

    def to_rows(wrapped):
        if max_steps is None:
            return spec.flatten(wrapped)
        return jnp.concatenate(
            [spec.flatten(wrapped.inner),
             wrapped.t.astype(jnp.float32)[..., None, :]], axis=-2)

    core_state = state.inner
    frames0 = None
    if num_stack is not None:
        frames0 = core_state.frames                    # (B, N, H, W)
        core_state = core_state.inner

    with jax.named_scope("cairl.layout"):
        rows = to_rows(core_state)                     # (S', B)
        fresh_rows = to_rows(fresh_states)             # (K, S', B)
        fobs_rows = jnp.swapaxes(fresh_obs, -1, -2)    # (K, O, B)

    with jax.named_scope("cairl.megastep"):
        new_rows, obs, tobs, reward, done, trunc = env_megastep(
            spec.step_rows, rows, acts.astype(jnp.float32), fresh_rows,
            fobs_rows, max_steps=max_steps, backend=backend,
            batch_block=batch_block)

    with jax.named_scope("cairl.layout"):
        inner = spec.unflatten(new_rows if max_steps is None
                               else new_rows[:spec.state_size])
        if max_steps is not None:
            inner = TimeLimitState(
                inner, new_rows[spec.state_size].astype(jnp.int32))
        done_b = done.astype(bool)
        info = {}
        if max_steps is not None:
            info["truncated"] = trunc.astype(bool)

    if not pixels:
        with jax.named_scope("cairl.layout"):
            new_state = AutoResetState(inner, final_keys)
            # The kernel computes in f32 rows; integer observation spaces
            # (the grid suite's MultiDiscrete cell codes) get their dtype
            # back here — values are small ints, exact through the f32
            # round-trip.
            odt = core.observation_space.dtype
            info["terminal_obs"] = jnp.swapaxes(tobs, -1, -2).astype(odt)
            out = new_state, Timestep(
                state=new_state, obs=jnp.swapaxes(obs, -1, -2).astype(odt),
                reward=reward, done=done_b, info=info)
        return out if active is None else _mask_inactive(state, *out,
                                                         active=active)

    # Pixel pipeline: rasterise the chunk's stepped (pre-reset) and fresh
    # frames in two batched on-device calls, then apply the frame-stack ring
    # and auto-reset selection — the same per-step render count as the vmap
    # path, minus all its per-step dispatch.
    pre = _render_obs_rows(core, spec, tobs, backend)        # (K·B, H, W)
    fresh_px = _render_obs_rows(core, spec, fobs_rows, backend)
    with jax.named_scope("cairl.frame_stack"):
        pre, fresh_px = (x.reshape((k, b) + x.shape[1:])
                         for x in (pre, fresh_px))
        if num_stack is None:
            obs_px = jnp.where(done_b[..., None, None], fresh_px, pre)
            tobs_px = pre
            new_inner = inner
        else:
            frames_t, obs_px, tobs_px = frame_stack(
                frames0, pre, fresh_px, done_b, backend=backend)
            new_inner = FrameStackState(inner, frames_t)
    new_state = AutoResetState(new_inner, final_keys)
    info["terminal_obs"] = tobs_px
    out = new_state, Timestep(state=new_state, obs=obs_px, reward=reward,
                              done=done_b, info=info)
    return out if active is None else _mask_inactive(state, *out,
                                                     active=active)
