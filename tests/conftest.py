import os
import sys

# tests run on 1 CPU device by design (the dry-run owns the 512-device env)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration tests")
