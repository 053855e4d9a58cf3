"""Shared pytest config: markers."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test (soaks, end-to-end sweeps); always in "
        "tier-1, deselectable with -m 'not slow' for quick local loops.",
    )
