"""Signature-policy evaluation: host oracle + batched device form.

The reference compiles a SignaturePolicy AST into closures over
([]msp.Identity, used []bool) with *greedy, order-dependent* semantics
(common/cauthdsl/cauthdsl.go:24-92):

- SignedBy(i): walk signers in order; the first NOT-yet-used signer that
  satisfies identities[i] is marked used and the leaf succeeds.
- NOutOf(n, rules): evaluate EVERY child in order (no short-circuit), each
  against a scratch copy of `used`; a succeeding child commits its copy
  back. Succeed iff >= n children succeeded.

These exact semantics (one signer satisfies at most one leaf along a
successful branch; order matters) must be reproduced bit-for-bit for
TRANSACTIONS_FILTER parity.

The batched form exploits that the policy is static per (channel,
chaincode) while transactions are many: principal matching happens on the
host (producing a bool satisfaction tensor), and the greedy walk becomes a
fixed sequence of vectorized mask updates over lanes = transactions. The
per-lane commit `used = where(ok, used_child, used)` IS Go's
copy-on-success, vectorized.
"""

from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fabric_tpu.policy.ast import NOutOf, SignaturePolicyEnvelope, SignedBy


def evaluate_host(env: SignaturePolicyEnvelope, sat: np.ndarray) -> bool:
    """Oracle evaluation for ONE transaction.

    sat: (num_signers, num_principals) bool — sat[s, p] true iff signer s
    satisfies identities[p] (and its signature verified; reference
    SignatureSetToValidIdentities drops non-verifying signers *before*
    evaluation, policies/policy.go:365-402).
    """
    num_signers = sat.shape[0]
    used = [False] * num_signers

    def walk(rule, used: List[bool]) -> bool:
        if isinstance(rule, SignedBy):
            for s in range(num_signers):
                if used[s]:
                    continue
                if sat[s, rule.index]:
                    used[s] = True
                    return True
            return False
        assert isinstance(rule, NOutOf)
        verified = 0
        for child in rule.rules:
            scratch = list(used)
            if walk(child, scratch):
                verified += 1
                used[:] = scratch
        return verified >= rule.n

    return walk(env.rule, used)


def compile_batched(
    env: SignaturePolicyEnvelope, num_signers: int
) -> Callable[[jax.Array], jax.Array]:
    """Compile the policy into a jittable function over batched satisfaction
    tensors: sat (B, num_signers, num_principals) bool -> (B,) bool."""

    def walk(rule, sat, used):
        # used: (B, S) bool; returns (ok (B,), used' (B, S))
        if isinstance(rule, SignedBy):
            elig = sat[:, :, rule.index] & ~used  # (B, S)
            ok = jnp.any(elig, axis=1)
            first = jnp.argmax(elig, axis=1)  # first True (argmax on bool)
            claim = jax.nn.one_hot(first, used.shape[1], dtype=bool) & ok[:, None]
            return ok, used | claim
        assert isinstance(rule, NOutOf)
        verified = jnp.zeros(used.shape[0], dtype=jnp.int32)
        for child in rule.rules:
            ok, used_child = walk(child, sat, used)
            verified = verified + ok.astype(jnp.int32)
            used = jnp.where(ok[:, None], used_child, used)
        return verified >= rule.n, used

    def run(sat: jax.Array) -> jax.Array:
        used0 = jnp.zeros((sat.shape[0], num_signers), dtype=bool)
        ok, _ = walk(env.rule, sat, used0)
        return ok

    return run


def compile_batched_numpy(
    env: SignaturePolicyEnvelope,
) -> Callable[[np.ndarray], np.ndarray]:
    """The batched greedy walk in vectorized NumPy: sat (B, S, P) bool ->
    (B,) bool, bit-identical to `compile_batched` / `evaluate_host`.

    This is the validator's default epilogue: policy circuits are a few
    dozen mask updates over small bool tensors — microseconds on host,
    whereas eager jnp dispatch pays a device round trip per op.
    The jax form remains for fused multi-channel device steps where the
    satisfaction tensor already lives on the device."""

    def walk(rule, sat, used):
        if isinstance(rule, SignedBy):
            elig = sat[:, :, rule.index] & ~used  # (B, S)
            ok = elig.any(axis=1)
            first = elig.argmax(axis=1)  # first True (argmax on bool)
            claim = np.zeros_like(used)
            claim[np.arange(used.shape[0]), first] = ok
            return ok, used | claim
        assert isinstance(rule, NOutOf)
        verified = np.zeros(used.shape[0], dtype=np.int32)
        for child in rule.rules:
            ok, used_child = walk(child, sat, used)
            verified = verified + ok.astype(np.int32)
            used = np.where(ok[:, None], used_child, used)
        return verified >= rule.n, used

    def run(sat: np.ndarray) -> np.ndarray:
        sat = np.asarray(sat, dtype=bool)
        used0 = np.zeros(sat.shape[:2], dtype=bool)
        ok, _ = walk(env.rule, sat, used0)
        return ok

    return run


def build_satisfaction_tensor(
    env: SignaturePolicyEnvelope,
    signer_principals: Sequence[Sequence[bool]],
) -> np.ndarray:
    """Stack per-signer principal-satisfaction rows into the (S, P) oracle
    input / one lane of the batched input."""
    num_p = len(env.identities)
    out = np.zeros((len(signer_principals), num_p), dtype=bool)
    for s, row in enumerate(signer_principals):
        assert len(row) == num_p
        out[s] = row
    return out
