"""Driver ``ecdsawaves``: the name ``configs/genledger-secp256k1.json`` gives
its driver. It IS ``sigwaves`` since PR 34: the scheme, the pool and the
reference come from the configuration, and the high-s probe and the two ECDSA
checks are keyed on the scheme there. A new deployment names ``sigwaves``."""
from drivers.sigwaves import run  # noqa: F401
