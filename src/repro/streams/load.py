"""In-run source load: a schedule of each source instance's generation
rate, the rate counterpart of :class:`repro.net.topology.LinkSchedule`.

The simulator evaluates, per tick,

    gen_i(t) = gen_rate_i · (1 + Σ_s amp[s,i]·sin(omega[s,i]·t + phase[s,i]))
                          · Π_{e active at t} scale[e,i]

clipped at zero. Two array families cover the input shapes of streaming
benchmarks (NEXmark's ``rateShape``):

  * **sinusoids** ``[S, I]`` — SINE: with the base rate at the midpoint of
    ``first`` and ``next``, the rate swings between them;
  * **steps** ``[E]`` times, ``[E, I]`` scales — over ``[t0_e, t1_e)``
    instance i's rate is multiplied by ``scale[e, i]`` (1 where the step
    does not touch it). SQUARE is a run of steps at ``next/first`` in
    alternate half-periods, one step row each, with the base at ``first``.

S = 0 and E = 0 mean a constant load: the simulator then skips the
schedule by shape. Padded sinusoid rows have zero amplitude and padded
steps never start (``t0 = inf``, scale 1), so a padded schedule scales by
exactly 1.0. ``compile_sim`` takes a schedule only for the instances that
generate (``gen_rate > 0``).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class LoadSchedule:
    n_instances: int
    sin_amp: np.ndarray     # [S, I]
    sin_omega: np.ndarray   # [S, I] rad/s
    sin_phase: np.ndarray   # [S, I] rad
    step_t0: np.ndarray     # [E] s (step active while t0 <= t < t1)
    step_t1: np.ndarray     # [E] s
    step_scale: np.ndarray  # [E, I] rate multiplier while active

    @classmethod
    def empty(cls, n_instances: int) -> "LoadSchedule":
        """No components (S = 0, E = 0): a constant load."""
        z = np.zeros((0, n_instances), np.float32)
        e = np.zeros((0,), np.float32)
        return cls(n_instances=n_instances, sin_amp=z, sin_omega=z.copy(),
                   sin_phase=z.copy(), step_t0=e, step_t1=e.copy(),
                   step_scale=z.copy())

    def _ids(self, instances) -> np.ndarray:
        ids = np.atleast_1d(np.asarray(instances, np.int32))
        if ids.size and (ids.min() < 0 or ids.max() >= self.n_instances):
            raise ValueError(f"instances {ids} out of range for "
                             f"{self.n_instances} instances")
        return ids

    # ---- builders (functional: each returns a new schedule) ----------
    def with_sine(self, period_s: float, first: float, next: float,
                  instances, phase: float = 0.0) -> "LoadSchedule":
        """SINE on ``instances``: their rate scales by
        1 + a·sin(2π t / period + phase), a = (next − first)/(next + first),
        so a base rate at the midpoint of ``first`` and ``next`` swings
        from ``first`` to ``next``."""
        ids = self._ids(instances)
        amp = np.zeros((1, self.n_instances), np.float32)
        amp[0, ids] = (next - first) / (next + first)
        omega = np.zeros((1, self.n_instances), np.float32)
        omega[0, ids] = 2.0 * np.pi / period_s
        ph = np.zeros((1, self.n_instances), np.float32)
        ph[0, ids] = phase
        return dataclasses.replace(
            self,
            sin_amp=np.concatenate([self.sin_amp, amp]),
            sin_omega=np.concatenate([self.sin_omega, omega]),
            sin_phase=np.concatenate([self.sin_phase, ph]))

    def with_steps(self, t0: float, t1: float, scale: float,
                   instances) -> "LoadSchedule":
        """Scale the rate of ``instances`` by ``scale`` over [t0, t1):
        one step row."""
        ids = self._ids(instances)
        row = np.ones((1, self.n_instances), np.float32)
        row[0, ids] = scale
        return dataclasses.replace(
            self,
            step_t0=np.concatenate([self.step_t0, [np.float32(t0)]]),
            step_t1=np.concatenate([self.step_t1, [np.float32(t1)]]),
            step_scale=np.concatenate([self.step_scale, row]))

    # ---- host-side evaluation (numpy; the simulator must match it) ---
    def scale_at(self, t) -> np.ndarray:
        """The rate multiplier of every instance at ``t`` (scalar or [T]):
        [I] or [T, I]. Steps are decided in float32, as the simulator
        decides them."""
        t = np.asarray(t, np.float64)
        ts = np.atleast_1d(t)
        scale = np.ones((ts.shape[0], self.n_instances))
        if self.sin_amp.shape[0]:
            scale = scale + np.sum(self.sin_amp[None] * np.sin(
                self.sin_omega[None] * ts[:, None, None]
                + self.sin_phase[None]), axis=1)
        ts32 = ts.astype(np.float32)
        for e in range(self.step_t0.shape[0]):
            on = (ts32 >= self.step_t0[e]) & (ts32 < self.step_t1[e])
            scale *= np.where(on[:, None],
                              self.step_scale[e][None].astype(np.float64), 1.0)
        scale = np.maximum(scale, 0.0)
        return scale[0] if t.ndim == 0 else scale
