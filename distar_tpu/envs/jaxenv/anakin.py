"""Anakin fused rollout: env step + policy forward + LSTM carry in one scan.

Podracer's Anakin layout (PAPERS.md) compiles the whole agent-environment
interaction into a single XLA program: ``AnakinRunner._rollout`` is one
jitted function — lane re-seeding, a ``lax.scan`` over ``unroll_len`` of
(observe -> sample_action -> env step), and the bootstrap observation —
whose carry is donated, so a training iteration performs zero per-step
host transfers (``device_pure_report`` proves it on the jaxpr; tests add a
``jax.transfer_guard`` witness). The emitted batch is already in the exact
time-major collate layout ``learner.data.fake_rl_batch`` documents, so
``RLLearner`` consumes it unchanged via ``AnakinDataLoader``.

Semantics mirror the host actor's window rules (actor/agent.py):

* a lane whose episode finishes mid-window keeps stepping a frozen env
  (core.step freezes state and zeroes rewards after done) while every mask
  and behaviour_logp is zeroed — the learner sees dead padding;
* finished lanes are re-seeded with FRESH scenarios (new fold of the carry
  key) at the next window boundary, with their LSTM carry zeroed;
* ``teacher_logit`` is the behaviour policy's own logits (self-teacher):
  the KL term of the loss is exactly zero, keeping the loss path intact
  without a second forward. A real teacher slots in via ``teacher_apply``.

**Away seat** (``opponent_seat=True``): opponent parameters become a
rollout *input* — the scan body runs a second (frozen) policy forward on
the away team's observation and feeds both action sets to ``core.step``,
so a league exploiter trains in-scan against a published main-agent
snapshot instead of the scripted opponent (ROADMAP item 2a). The emitted
batch additionally carries per-lane episode outcomes (``match_result``)
which :class:`AnakinDataLoader` strips host-side into a results buffer
for league/arena match reporting. The default single-policy path is
untouched: same jitted entry, same key-split schedule, bit-identical
batches.

The runner is a single-device building block: vmap/shard_map it across the
``parallel/`` mesh by mapping ``rollout`` over a leading key/params axis.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...lib import actions as ACT
from ...lib import features as F
from ...obs import get_registry
from .core import EnvConfig, micro_legal_mask, reset, step
from .obs import observe
from .scenario import ScenarioConfig, ScenarioGenerator

# Per-action-type head-relevance LUTs (static numpy, baked into the jaxpr):
# actions_mask[head][t, b] = LUT[head][action_type] * step_mask, matching
# the host actor's per-step mask derivation from the ACTIONS spec flags.
_HEAD_LUT = {
    "action_type": np.ones(ACT.NUM_ACTIONS, np.float32),
    "delay": np.ones(ACT.NUM_ACTIONS, np.float32),
    "queued": ACT.QUEUED_MASK.astype(np.float32),
    "selected_units": ACT.SELECTED_UNITS_MASK.astype(np.float32),
    "target_unit": ACT.TARGET_UNIT_MASK.astype(np.float32),
    "target_location": ACT.TARGET_LOCATION_MASK.astype(np.float32),
}

# jaxpr primitives that would mean the scanned loop leaves the device
_IMPURE_PRIMITIVES = ("callback", "infeed", "outfeed", "host_local_array")


def _scan_eqns(jaxpr, found):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(tag in name for tag in _IMPURE_PRIMITIVES):
            found.append(name)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                _scan_eqns(inner, found)
            if isinstance(v, (list, tuple)):
                for w in v:
                    inner = getattr(w, "jaxpr", None)
                    if inner is not None:
                        _scan_eqns(inner, found)


def device_pure_report(fn: Callable, *args) -> dict:
    """Trace ``fn(*args)`` and scan the full jaxpr (recursively through
    scan/cond/pjit bodies) for host-transfer primitives.

    Returns ``{"pure": bool, "offending": [primitive names]}`` — the
    acceptance witness that nothing inside the fused loop calls back to
    the host."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    found: list = []
    _scan_eqns(jaxpr.jaxpr, found)
    return {"pure": not found, "offending": found}


class AnakinRunner:
    """Fused rollout producer for one device.

    Parameters
    ----------
    model: the flax ``Model`` (flagship or smoke config) — ``sample_action``
        drives every head; hidden dims are read from ``model.cfg``.
    batch_size: B, the number of vmapped env lanes (>= 1024 for the
        acceptance run).
    unroll_len: T, steps per trajectory window.
    restrict_micro: confine sampling to the micro-battle action-type
        vocabulary via ``sample_action``'s legal_mask (default True — the
        environment ignores macro actions anyway, this keeps behaviour
        probability mass on executable commands).
    teacher_apply: optional ``(obs_leaves..., hidden, action, sun) ->
        logits`` for a real teacher; default self-teacher.
    opponent_seat: compile the two-policy rollout — ``rollout`` then takes
        frozen ``opponent_params`` driving the away team and the batch
        carries ``match_result`` episode outcomes. Off by default; the
        single-policy path is bit-identical to pre-league behaviour.
    """

    def __init__(self, model, batch_size: int, unroll_len: int,
                 env_cfg: EnvConfig = EnvConfig(),
                 scenario_cfg: Optional[ScenarioConfig] = None,
                 seed: int = 0, restrict_micro: bool = True,
                 teacher_apply: Optional[Callable] = None,
                 opponent_seat: bool = False):
        self.model = model
        self.B = int(batch_size)
        self.T = int(unroll_len)
        self.env_cfg = env_cfg
        self.gen = ScenarioGenerator(
            scenario_cfg
            if scenario_cfg is not None
            else ScenarioConfig(units_per_squad=env_cfg.units_per_squad))
        if self.gen.cfg.units_per_squad != env_cfg.units_per_squad:
            raise ValueError(
                "scenario_cfg.units_per_squad must match env_cfg "
                f"({self.gen.cfg.units_per_squad} != {env_cfg.units_per_squad})")
        lstm = model.cfg["encoder"]["core_lstm"]
        self._hidden_size = int(lstm["hidden_size"])
        self._hidden_layers = int(lstm["num_layers"])
        self._legal = jnp.asarray(micro_legal_mask()) if restrict_micro else None
        self._teacher_apply = teacher_apply
        self._seed = seed
        self.opponent_seat = bool(opponent_seat)
        self._rollout = jax.jit(self._rollout_impl, donate_argnums=(1,))
        if self.opponent_seat:
            # separate jitted entry: the opponent path has a different
            # carry structure (away LSTM state) and an extra params input
            self._rollout_opp = jax.jit(
                self._rollout_opp_impl, donate_argnums=(2,))

    # ---------------------------------------------------------------- carry
    def _zero_hidden(self):
        return tuple(
            (jnp.zeros((self.B, self._hidden_size), jnp.float32),
             jnp.zeros((self.B, self._hidden_size), jnp.float32))
            for _ in range(self._hidden_layers))

    def init_carry(self, key: Optional[jax.Array] = None):
        """(states, hidden, key): B env lanes + zero LSTM carries. With the
        away seat enabled: (states, hidden, opp_hidden, key)."""
        if key is None:
            key = jax.random.PRNGKey(self._seed)
        key, k_scn = jax.random.split(key)
        scn = self.gen.batch(k_scn, self.B)
        states = jax.vmap(partial(reset, self.env_cfg))(scn)
        hidden = self._zero_hidden()
        # the carry is donated to the fused rollout; aliased leaves (e.g.
        # reset's order_pos sharing pos's buffer) would be donated twice,
        # so force every leaf onto its own buffer
        states = jax.tree.map(lambda x: jnp.array(x, copy=True), states)
        if self.opponent_seat:
            return states, hidden, self._zero_hidden(), key
        return states, hidden, key

    # -------------------------------------------------------------- rollout
    def _sample(self, params, obs, hidden, key):
        return self.model.apply(
            params, obs["spatial_info"], obs["entity_info"], obs["scalar_info"],
            obs["entity_num"], hidden, key, self._legal,
            method=self.model.sample_action)

    def _emit_y(self, cfg, out, obs, hid, st, rew, done, step_mask):
        """One scan step's learner-batch slice (home perspective) — shared
        verbatim between the single-policy and away-seat bodies."""
        action = out["action_info"]
        sun = out["selected_units_num"]
        if self._teacher_apply is not None:
            teacher = self._teacher_apply(obs, hid, action, sun)
        else:
            teacher = out["logit"]
        logp = out["action_logp"]
        zero = jnp.zeros((self.B,), jnp.float32)
        return {
            "obs": obs,
            "action_info": action,
            "selected_units_num": sun,
            "behaviour_logp": {
                k: v * (step_mask[:, None] if v.ndim == 2 else step_mask)
                for k, v in logp.items()},
            "teacher_logit": teacher,
            "reward": {
                "winloss": rew["winloss"][:, 0] * step_mask,
                "battle": rew["battle"][:, 0] * step_mask,
                "build_order": zero, "built_unit": zero,
                "effect": zero, "upgrade": zero,
            },
            "step": (st.t * cfg.loops_per_step).astype(jnp.float32),
            "done": done.astype(jnp.float32),
            "mask": {
                "actions_mask": {
                    k: jnp.asarray(lut)[action["action_type"]] * step_mask
                    for k, lut in _HEAD_LUT.items()},
                "build_order_mask": zero,
                "built_unit_mask": zero,
                "effect_mask": step_mask,
                "cum_action_mask": step_mask,
                "step_mask": step_mask,
            },
        }

    def _rollout_impl(self, params, carry):
        cfg = self.env_cfg
        states, hidden, key = carry
        key, k_seed, k_scan = jax.random.split(key, 3)

        # window boundary: finished lanes get fresh scenarios + zero carry
        fresh_scn = jax.vmap(self.gen.generate)(jax.random.split(k_seed, self.B))
        fresh = jax.vmap(partial(reset, cfg))(fresh_scn)
        d = states.done

        def lane_where(old, new):
            return jnp.where(d.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)

        states = jax.tree.map(lane_where, states, fresh)
        hidden = tuple((jnp.where(d[:, None], 0.0, h), jnp.where(d[:, None], 0.0, c))
                       for h, c in hidden)
        hidden0 = hidden

        observe_b = jax.vmap(partial(observe, cfg), in_axes=(0, None))
        step_b = jax.vmap(partial(step, cfg))

        def body(scan_carry, k_t):
            st, hid = scan_carry
            prev_done = st.done
            obs = observe_b(st, 0)
            out = self._sample(params, obs, hid, k_t)
            action = out["action_info"]
            sun = out["selected_units_num"]
            nst, rew, done, _winner = step_b(st, action, sun)
            step_mask = (~prev_done).astype(jnp.float32)
            y = self._emit_y(cfg, out, obs, hid, st, rew, done, step_mask)
            return (nst, out["hidden_state"]), y

        (states, hidden), ys = jax.lax.scan(
            body, (states, hidden), jax.random.split(k_scan, self.T))

        batch = self._assemble_batch(observe_b, states, hidden0, ys)
        return (states, hidden, key), batch

    def _assemble_batch(self, observe_b, states, hidden0, ys):
        boot = observe_b(states, 0)
        obs_full = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b[None]], axis=0), ys["obs"], boot)
        sun = ys["selected_units_num"]
        return {
            "spatial_info": obs_full["spatial_info"],
            "entity_info": obs_full["entity_info"],
            "scalar_info": obs_full["scalar_info"],
            "entity_num": obs_full["entity_num"],
            "hidden_state": hidden0,
            "action_info": ys["action_info"],
            "selected_units_num": sun,
            "behaviour_logp": ys["behaviour_logp"],
            "teacher_logit": ys["teacher_logit"],
            "reward": ys["reward"],
            "step": ys["step"],
            "done": ys["done"],
            "mask": dict(
                ys["mask"],
                selected_units_mask=(
                    jnp.arange(F.MAX_SELECTED_UNITS_NUM)[None, None, :]
                    < sun[..., None]),
            ),
            "model_last_iter": jnp.zeros((self.B,), jnp.float32),
        }

    def _rollout_opp_impl(self, params, opp_params, carry):
        """Two-policy sibling of ``_rollout_impl``: the away team is driven
        by a frozen opponent policy (its own LSTM carry rides the donated
        carry), and per-step ``(winner, finished)`` outcomes are emitted so
        the host can report league matches. The home side's batch semantics
        are identical to the single-policy path."""
        cfg = self.env_cfg
        states, hidden, opp_hidden, key = carry
        key, k_seed, k_scan = jax.random.split(key, 3)

        fresh_scn = jax.vmap(self.gen.generate)(jax.random.split(k_seed, self.B))
        fresh = jax.vmap(partial(reset, cfg))(fresh_scn)
        d = states.done

        def lane_where(old, new):
            return jnp.where(d.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)

        states = jax.tree.map(lane_where, states, fresh)
        hidden = tuple((jnp.where(d[:, None], 0.0, h), jnp.where(d[:, None], 0.0, c))
                       for h, c in hidden)
        opp_hidden = tuple(
            (jnp.where(d[:, None], 0.0, h), jnp.where(d[:, None], 0.0, c))
            for h, c in opp_hidden)
        hidden0 = hidden

        observe_b = jax.vmap(partial(observe, cfg), in_axes=(0, None))
        step_b = jax.vmap(partial(step, cfg))

        def body(scan_carry, k_t):
            st, hid, opp_hid = scan_carry
            prev_done = st.done
            # independent streams per seat (winrate.head_to_head idiom)
            ka, kb = jax.random.split(k_t)
            obs = observe_b(st, 0)
            out = self._sample(params, obs, hid, ka)
            obs_away = observe_b(st, 1)
            out_away = self._sample(opp_params, obs_away, opp_hid, kb)
            nst, rew, done, winner = step_b(
                st, out["action_info"], out["selected_units_num"],
                out_away["action_info"], out_away["selected_units_num"])
            step_mask = (~prev_done).astype(jnp.float32)
            y = self._emit_y(cfg, out, obs, hid, st, rew, done, step_mask)
            y["match_winner"] = winner
            y["match_finished"] = done & ~prev_done
            return (nst, out["hidden_state"], out_away["hidden_state"]), y

        (states, hidden, opp_hidden), ys = jax.lax.scan(
            body, (states, hidden, opp_hidden), jax.random.split(k_scan, self.T))

        winner = ys.pop("match_winner")
        finished = ys.pop("match_finished")
        batch = self._assemble_batch(observe_b, states, hidden0, ys)
        batch["match_result"] = {
            "winner": winner, "finished": finished,
            "steps": ys["step"],
        }
        return (states, hidden, opp_hidden, key), batch

    def rollout(self, params, carry, opponent_params=None):
        """One fused window: (new_carry, learner batch [T, B] on device).
        With ``opponent_seat``, ``opponent_params`` drive the away team and
        the batch gains a ``match_result`` leaf (host-stripped by the
        loader before the learner sees the batch)."""
        if self.opponent_seat:
            assert opponent_params is not None, \
                "opponent_seat runner needs opponent_params"
            return self._rollout_opp(params, opponent_params, carry)
        assert opponent_params is None, \
            "construct AnakinRunner(opponent_seat=True) to pass opponent_params"
        return self._rollout(params, carry)

    def purity_report(self, params, carry, opponent_params=None) -> dict:
        """Jaxpr audit of the full fused window (scan body included)."""
        if self.opponent_seat:
            return device_pure_report(
                self._rollout_opp_impl, params, opponent_params, carry)
        return device_pure_report(self._rollout_impl, params, carry)


class AnakinDataLoader:
    """Iterator feeding ``RLLearner.set_dataloader`` from an AnakinRunner.

    The learner's lazy ``_setup_state`` pulls one batch for shapes before it
    owns params, so the loader bootstraps its own parameter pytree (one
    ``model.init``) and switches to ``params_provider`` (the learner's live
    train state) as soon as it returns one — on-policy after the first
    window. Batches stay on device end to end: the learner's ``shard_batch``
    hands device arrays to ``device_put`` as they are (no host round-trip;
    a leaf in another sharding than the step's is re-laid on the device and
    counted in ``distar_feeder_relaid_leaves_total``).

    With an ``opponent_seat`` runner, ``opponent_provider`` supplies the
    frozen away-team parameters each window (a league snapshot published
    by the coordinator; defaults to the bootstrap pytree — a frozen copy
    of the initial policy). The per-lane episode outcomes are stripped
    host-side into a results buffer; ``drain_results()`` hands them to the
    league learner loop for match reporting.
    """

    def __init__(self, runner: AnakinRunner,
                 params_provider: Optional[Callable] = None,
                 opponent_provider: Optional[Callable] = None):
        self.runner = runner
        self._params_provider = params_provider or (lambda: None)
        self._opponent_provider = opponent_provider or (lambda: None)
        self._bootstrap_params = None
        self._carry = None
        self._results: list = []
        reg = get_registry()
        reg.gauge("distar_rollout_plane_backend",
                  "active rollout-plane backend (1 = active)",
                  backend="anakin").set(1)
        self._g_rate = reg.gauge(
            "distar_anakin_env_steps_per_s",
            "fused-loop environment steps per wall second")
        self._c_batches = reg.counter(
            "distar_anakin_batches_total", "trajectory windows produced")
        self._c_episodes = reg.counter(
            "distar_env_episodes_total", "jaxenv episodes finished",
            backend="anakin")
        self._h_window = reg.histogram(
            "distar_anakin_window_seconds", "wall time per fused window")

    def _bootstrap(self):
        if self._bootstrap_params is None:
            r = self.runner
            carry = r.init_carry(jax.random.PRNGKey(r._seed))
            states, hidden = carry[0], carry[1]
            obs = jax.vmap(partial(observe, r.env_cfg), in_axes=(0, None))(states, 0)
            self._bootstrap_params = r.model.init(
                jax.random.PRNGKey(r._seed),
                obs["spatial_info"], obs["entity_info"], obs["scalar_info"],
                obs["entity_num"], hidden, jax.random.PRNGKey(r._seed + 1),
                method=r.model.sample_action)
        return self._bootstrap_params

    def _params(self):
        live = self._params_provider()
        if live is not None:
            return live
        return self._bootstrap()

    def _opponent_params(self):
        frozen = self._opponent_provider()
        if frozen is not None:
            return frozen
        return self._bootstrap()

    def drain_results(self) -> list:
        """Episode outcomes accumulated since the last drain (opponent-seat
        windows only): ``[{"winner": "home"|"away"|"draw", "steps": n}]``
        in finish order — the league learner's match-report feed."""
        out, self._results = self._results, []
        return out

    def __iter__(self):
        return self

    def __next__(self):
        if self._carry is None:
            self._carry = self.runner.init_carry()
        t0 = time.perf_counter()
        try:
            if self.runner.opponent_seat:
                self._carry, batch = self.runner.rollout(
                    self._params(), self._carry,
                    opponent_params=self._opponent_params())
                ended = self._collect_results(batch.pop("match_result"))
            else:
                self._carry, batch = self.runner.rollout(
                    self._params(), self._carry)
                # one deliberate host sync per window for honest wall-clock
                # metrics
                ended = int(jnp.sum(batch["done"][-1]))
        except Exception:
            # the fused call donates the carry; a failure mid-window leaves
            # the old carry pointing at deleted buffers, which would poison
            # every retry — drop it so a supervised restart re-initialises
            self._carry = None
            raise
        dt = max(time.perf_counter() - t0, 1e-9)
        self._g_rate.set(self.runner.B * self.runner.T / dt)
        self._h_window.observe(dt)
        self._c_batches.inc()
        if ended:
            self._c_episodes.inc(ended)
        return batch

    def _collect_results(self, match_result: dict) -> int:
        """Strip the device-side outcome leaves into host records (the one
        host sync the opponent-seat window pays, replacing the metrics
        sync of the default path)."""
        from .core import WINNER_AWAY, WINNER_HOME

        finished = np.asarray(match_result["finished"])  # [T, B] bool
        winner = np.asarray(match_result["winner"])      # [T, B] i32
        steps = np.asarray(match_result["steps"])        # [T, B] f32
        names = {WINNER_HOME: "home", WINNER_AWAY: "away"}
        for t, b in zip(*np.nonzero(finished)):
            self._results.append({
                "winner": names.get(int(winner[t, b]), "draw"),
                "steps": float(steps[t, b]),
            })
        return int(finished.sum())
