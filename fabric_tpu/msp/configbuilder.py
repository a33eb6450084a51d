"""Filesystem MSP material (reference msp/configbuilder.go + the
cryptogen output layout integration/nwo consumes).

Directory layout written/read here matches Fabric's crypto-config tree:

  <root>/<org-domain>/
    msp/cacerts/ca.<domain>-cert.pem
    msp/admincerts/Admin@<domain>-cert.pem
    peers/<peer>.<domain>/msp/{signcerts,keystore,cacerts}
    users/<user>@<domain>/msp/{signcerts,keystore,cacerts}

Keys are PKCS#8 PEM (cryptogen's output format).
"""

from __future__ import annotations

import os
from typing import List, Optional

try:  # guarded: the PEM/X.509 material here needs the cryptography
    # package, but the module must import in minimal environments so
    # tier-1 collection stays clean (ladder: crypto/bccsp.py)
    from cryptography import x509
    from cryptography.hazmat.primitives import serialization
except ImportError:  # pragma: no cover - exercised in minimal envs
    x509 = serialization = None  # type: ignore

from fabric_tpu.msp.cryptogen import NodeIdentity, Org
from fabric_tpu.msp.identity import MSP, MSPConfig, NodeOUs
from fabric_tpu.msp.signer import SigningIdentity


def _write(path: str, data: bytes) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(data)


def _key_pem(node: NodeIdentity) -> bytes:
    return node.key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption(),
    )


def write_org_dir(org: Org, root: str) -> str:
    """cryptogen generate: materialize one org's tree; returns org dir."""
    org_dir = os.path.join(root, org.ca.org_name)
    _write(
        os.path.join(org_dir, "msp", "cacerts", f"ca.{org.ca.org_name}-cert.pem"),
        org.ca.cert_pem,
    )
    _write(
        os.path.join(
            org_dir, "msp", "admincerts", f"Admin@{org.ca.org_name}-cert.pem"
        ),
        org.admin.cert_pem,
    )
    for kind, nodes in (("peers", org.peers), ("users", [org.admin] + org.users)):
        for node in nodes:
            base = os.path.join(org_dir, kind, node.name, "msp")
            _write(
                os.path.join(base, "signcerts", f"{node.name}-cert.pem"),
                node.cert_pem,
            )
            _write(os.path.join(base, "keystore", "priv_sk"), _key_pem(node))
            _write(
                os.path.join(base, "cacerts", f"ca.{org.ca.org_name}-cert.pem"),
                org.ca.cert_pem,
            )
            if kind == "peers":
                # TLS material alongside the MSP (reference cryptogen's
                # tls/ folder: server.crt/server.key/ca.crt) so TLS
                # configs have files to point at out of the box
                pair = org.ca.enroll_tls(node.name)
                tls_dir = os.path.join(org_dir, kind, node.name, "tls")
                _write(os.path.join(tls_dir, "server.crt"), pair.cert_pem)
                _write(os.path.join(tls_dir, "server.key"), pair.key_pem)
                _write(os.path.join(tls_dir, "ca.crt"), pair.ca_pem)
    return org_dir


def load_msp_config(org_msp_dir: str, msp_id: str) -> MSPConfig:
    """msp/configbuilder.go GetVerifyingMspConfig: read cacerts/admincerts
    from an org-level msp dir."""

    def read_all(sub: str) -> List[bytes]:
        d = os.path.join(org_msp_dir, sub)
        if not os.path.isdir(d):
            return []
        return [
            open(os.path.join(d, f), "rb").read() for f in sorted(os.listdir(d))
        ]

    roots = read_all("cacerts")
    if not roots:
        raise ValueError(f"no cacerts in {org_msp_dir}")
    return MSPConfig(
        msp_id=msp_id,
        root_certs=roots,
        intermediate_certs=read_all("intermediatecerts"),
        admins=read_all("admincerts"),
        revocation_list=read_all("crls"),
        node_ous=NodeOUs(),
    )


def _default_msp_provider():
    """MSP cert-chain checks and local signing are single-op host
    crypto — work TPUProvider delegates to the software path anyway —
    so config-loaded MSPs/signers default to the SOFTWARE provider
    rather than default_provider(): the latter probes for an
    accelerator, and a backend init that hangs must never stall a CLI
    client or a node's MSP setup. Callers that
    really want a device-backed provider pass it explicitly."""
    from fabric_tpu.crypto.bccsp import SoftwareProvider

    return SoftwareProvider()


def load_msp(org_msp_dir: str, msp_id: str, provider=None) -> MSP:
    return MSP(
        load_msp_config(org_msp_dir, msp_id),
        provider or _default_msp_provider(),
    )


def load_signing_identity(
    node_msp_dir: str, msp_id: str, provider=None
) -> SigningIdentity:
    """msp/configbuilder.go GetLocalMspConfig: signcerts + keystore."""
    if x509 is None:
        raise RuntimeError(
            "the 'cryptography' package is required to load X.509 "
            "signing material (configbuilder)"
        )
    sign_dir = os.path.join(node_msp_dir, "signcerts")
    certs = sorted(os.listdir(sign_dir))
    if not certs:
        raise ValueError(f"no signcerts in {node_msp_dir}")
    cert_pem = open(os.path.join(sign_dir, certs[0]), "rb").read()
    cert = x509.load_pem_x509_certificate(cert_pem)
    name = cert.subject.get_attributes_for_oid(
        x509.NameOID.COMMON_NAME
    )[0].value
    key_dir = os.path.join(node_msp_dir, "keystore")
    keys = sorted(os.listdir(key_dir)) if os.path.isdir(key_dir) else []
    key = None
    token_ski = b""
    if keys:
        key = serialization.load_pem_private_key(
            open(os.path.join(key_dir, keys[0]), "rb").read(), password=None
        )
    elif provider is not None and hasattr(provider, "sign_by_ski"):
        # HSM deployment (reference msp + bccsp/pkcs11): no keystore on
        # disk — the private key lives on the token, addressed by the
        # SKI derived from the cert's public key (sha256 over the
        # uncompressed EC point, pkcs11.go's ski convention)
        import hashlib

        point = cert.public_key().public_bytes(
            serialization.Encoding.X962,
            serialization.PublicFormat.UncompressedPoint,
        )
        token_ski = hashlib.sha256(point).digest()
    else:
        raise ValueError(f"no keystore entries in {node_msp_dir}")
    node = NodeIdentity(
        name=name,
        cert_pem=cert_pem,
        key=key,
        msp_id=msp_id,
        token_ski=token_ski,
    )
    return SigningIdentity(node, provider or _default_msp_provider())
