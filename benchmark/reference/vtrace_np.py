"""Plain numpy reference of the IMPALA loss (Espeholt et al. 2018,
arXiv:1802.01561, section 4.1 and equation 1), float64, a Python loop over
time. Imports nothing from `ray_tpu`.

Given the target policy's logits [T, B, A] and values [T, B] (which the
caller gets from the system's own network: the reference checks the
V-trace targets and the three loss terms, not the convolutions), the
behaviour policy's log-probabilities of the taken actions, rewards,
terminal flags and the bootstrap value V(x_T):

    rho_t = min(rho_bar, pi(a_t|x_t) / mu(a_t|x_t)),  c_t = min(1, ratio)
    delta_t = rho_t (r_t + gamma_t V(x_t+1) - V(x_t)),  gamma_t = gamma (1 - done_t)
    v_t - V(x_t) = delta_t + gamma_t c_t (v_t+1 - V(x_t+1)),  backwards from 0 at T
    advantage_t = min(pg_rho_bar, ratio) (r_t + gamma_t v_t+1 - V(x_t))
    loss = -mean(log pi(a_t|x_t) advantage_t)
           + vf_coeff * 0.5 mean((v_t - V(x_t))^2) - entropy_coeff * mean(H(pi(.|x_t)))

with v_t and the advantages treated as constants.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def log_softmax(logits: np.ndarray) -> np.ndarray:
    z = logits - logits.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def impala_loss(logits, values, batch: Dict[str, Any], *, gamma: float,
                vf_loss_coeff: float, entropy_coeff: float,
                clip_rho_threshold: float = 1.0,
                clip_pg_rho_threshold: float = 1.0) -> Dict[str, float]:
    logits = np.asarray(logits, np.float64)
    values = np.asarray(values, np.float64)
    actions = np.asarray(batch["actions"]).astype(np.int64)
    rewards = np.asarray(batch["rewards"], np.float64)
    dones = np.asarray(batch["dones"]).astype(np.float64)
    behaviour_logp = np.asarray(batch["behaviour_logp"], np.float64)
    bootstrap = np.asarray(batch["bootstrap_value"], np.float64)
    t_len, _b = actions.shape

    logp_all = log_softmax(logits)
    target_logp = np.take_along_axis(
        logp_all, actions[..., None], axis=-1)[..., 0]
    ratio = np.exp(target_logp - behaviour_logp)
    rho = np.minimum(ratio, clip_rho_threshold)
    c = np.minimum(ratio, 1.0)
    discount = gamma * (1.0 - dones)

    next_values = np.concatenate([values[1:], bootstrap[None]], axis=0)
    delta = rho * (rewards + discount * next_values - values)
    vs_minus_v = np.zeros_like(values)
    acc = np.zeros_like(bootstrap)
    for t in range(t_len - 1, -1, -1):
        acc = delta[t] + discount[t] * c[t] * acc
        vs_minus_v[t] = acc
    vs = vs_minus_v + values
    next_vs = np.concatenate([vs[1:], bootstrap[None]], axis=0)
    advantage = np.minimum(ratio, clip_pg_rho_threshold) * (
        rewards + discount * next_vs - values)

    policy_loss = -np.mean(target_logp * advantage)
    vf_loss = 0.5 * np.mean((vs - values) ** 2)
    entropy = np.mean(-(np.exp(logp_all) * logp_all).sum(axis=-1))
    return {"total_loss": float(policy_loss + vf_loss_coeff * vf_loss
                                - entropy_coeff * entropy),
            "policy_loss": float(policy_loss), "vf_loss": float(vf_loss),
            "entropy": float(entropy)}
