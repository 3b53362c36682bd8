"""Shared on-device exploration collection.

One jitted program: vmapped segment rollout (auto-reset, noise-state
threading) + truncation-exact n-step collapse. Both trainers consume it —
the fully on-device loop (``runtime/on_device.py``) appends the result to
its device ring, the host-replay sync trainer (``runtime/trainer.py``)
fetches the flat block and bulk-inserts it into the host buffer. ONE
implementation of the n-step window math, where the reference carries two
that disagree on the discount (``ddpg.py:129`` vs ``:155``, SURVEY.md
quirk #5).

Windows never span segment boundaries: the last up-to-(n−1) steps of a
segment bootstrap early with the exact ``γ^m`` of their shortened window —
a valid m-step Bellman target, the same convention as episode truncation
(:func:`d4pg_tpu.ops.nstep_returns` with ``truncations``).

DOCUMENTED DEVIATION from the reference's (intended) continuous n-step
writer: with 32-step segments and n=5, ~12.5% of stored transitions carry a
shortened (m<n) window, which slightly shifts the target distribution
toward 1-step-like backups at segment edges. Every stored target remains an
exact m-step Bellman target, so this is a sampling-mix difference, not a
correctness bug (advisor round-1 review). If exact reference parity ever
matters, ring the last n−1 transitions of each segment into the next
collect call; the async/HER paths already use the continuous
``NStepWriter`` and are unaffected.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from d4pg_tpu.agent import act_deterministic
from d4pg_tpu.agent.d4pg import (
    act_on_window, make_noise, noisy_explore, push_observation,
)
from d4pg_tpu.agent.state import D4PGConfig
from d4pg_tpu.envs.rollouts import rollout
from d4pg_tpu.ops import nstep_returns


def policy_state_fns(config: D4PGConfig, noise_fns):
    """``(init, reset)`` of what a collecting policy carries from step to
    step: the noise state, and with a torso each env's last ``window``
    observations and how many of them belong to the running episode (a
    torso policy acts on the window; an episode's start empties it, unless
    the torso's windows span their stream: then the history is kept across
    resets and only the noise starts anew)."""
    noise_init, _, noise_reset = noise_fns
    if config.torso is None:
        return noise_init, noise_reset

    def init():
        window = jnp.zeros((config.torso.window, config.obs_dim), jnp.float32)
        return noise_init(), window, jnp.zeros((), jnp.int32)

    def reset(state):
        if config.torso.span == "stream":
            return (noise_reset(state[0]),) + tuple(state[1:])
        return noise_reset(state[0]), jnp.zeros_like(state[1]), jnp.zeros_like(state[2])

    return init, reset


def make_segment_collector(
    config: D4PGConfig,
    env,
    num_envs: int,
    segment_len: int,
    noise_fns=None,
    return_traj: bool = True,
):
    """Build a jitted ``collect(actor_params, env_states, obs, noise_states,
    key, noise_scale) -> (env_states, obs, noise_states, flat, traj)``.

    ``flat`` is a dict of ``[num_envs*segment_len]`` n-step-collapsed
    transitions (obs, action, reward=R^(m), next_obs=s_{t+m},
    discount=γ^m·(1−terminal)); ``traj`` is the raw segment for metrics.
    ``noise_scale`` is a traced scalar — schedules don't retrace.
    ``noise_states`` is what :func:`policy_state_fns` makes (``collect.
    policy_state_init``). With a torso ``actor_params`` are
    ``agent.d4pg.acting_params`` and ``flat`` is time-major — row
    ``t·num_envs + e`` — so an env's consecutive steps lie ``num_envs`` rows
    apart in the ring (``TorsoConfig.row_stride``), across segments too.

    ``return_traj=False`` returns ``None`` for ``traj`` so XLA prunes the
    raw-segment outputs from the program — callers that only consume
    ``flat`` (the host sync trainer) otherwise pay HBM writes for the full
    [N, L] obs/next_obs blocks as jit outputs (2× the flat block for pixel
    envs). Callers that trace this inside their own jit (the on-device
    trainer) get that pruning for free and can keep ``traj`` for metrics.
    """
    noise_fns = noise_fns or make_noise(config)
    noise_sample = noise_fns[1]
    state_init, state_reset = policy_state_fns(config, noise_fns)
    n_new = num_envs * segment_len

    @jax.jit
    def collect(actor_params, env_states, obs, noise_states, key, noise_scale):
        def policy(o, k, pstate):
            if config.torso is None:
                a = act_deterministic(config, actor_params, o[None])[0]
                return noisy_explore(config, noise_sample, a, k, pstate, noise_scale)
            nstate, window, count = pstate
            window, count, valid = push_observation(window, count, o)
            a = act_on_window(config, actor_params, window[None], valid[None])[0]
            a, nstate = noisy_explore(config, noise_sample, a, k, nstate, noise_scale)
            return a, (nstate, window, count)

        def one(env_state, o, nstate, k):
            return rollout(
                env, policy, k, segment_len,
                init_state=env_state, init_obs=o,
                policy_state=nstate, policy_state_reset=state_reset,
            )

        keys = jax.random.split(key, num_envs)
        env_states, obs, noise_states, traj = jax.vmap(one)(
            env_states, obs, noise_states, keys
        )

        def collapse(rew, term, trunc, tr_obs, tr_act, tr_next):
            rets, boots, offs = nstep_returns(
                rew, term, config.gamma, config.n_step, truncations=trunc
            )
            # bootstrap state s_{t+m} is next_obs[t + m - 1]
            idx = jnp.clip(jnp.arange(rew.shape[0]) + offs - 1, 0, rew.shape[0] - 1)
            return {
                "obs": tr_obs,
                "action": tr_act,
                "reward": rets,
                "next_obs": tr_next[idx],
                "discount": boots,
            }

        flat = jax.vmap(collapse)(
            traj.reward, traj.terminated, traj.truncated,
            traj.obs, traj.action, traj.next_obs,
        )
        if config.torso is not None:      # time-major: an env's rows num_envs apart
            flat = jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1), flat)
        flat = jax.tree_util.tree_map(
            lambda x: x.reshape((n_new,) + x.shape[2:]), flat
        )
        return env_states, obs, noise_states, flat, traj if return_traj else None

    collect.policy_state_init = state_init
    return collect
