"""The arrows of the architecture point down, read from the source alone
(AST; nothing is imported, so no JAX):

client -> worker -> primary -> crypto/backend.py -> ops/, with consensus
beside the primary.  `ops/` is the device layer: it imports no protocol
package, and the crypto seam is the only module that imports it.
"""

import ast
import os

import pytest

PACKAGE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "narwhal_tpu"
)
OPS = os.path.join(PACKAGE, "ops")
PROTOCOL = {"consensus", "primary", "worker", "node", "network", "faults"}
SEAM = os.path.join("crypto", "backend.py")


def imported_modules(path):
    """Absolute dotted names of everything ``path`` imports, at any depth
    (deferred imports inside functions count), relative ones resolved."""
    rel = os.path.relpath(path, os.path.dirname(PACKAGE))
    package = rel.split(os.sep)[:-1]
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - node.level + 1] if node.level else []
            base = base + (node.module.split(".") if node.module else [])
            found.add(".".join(base))
            # `from .. import ops` names the module in the alias.
            found.update(".".join(base + [alias.name]) for alias in node.names)
    return found


def package_files():
    for root, dirs, files in os.walk(PACKAGE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(root, name)


OPS_MODULES = sorted(f for f in os.listdir(OPS) if f.endswith(".py"))


@pytest.mark.parametrize("module", OPS_MODULES)
def test_ops_imports_no_protocol_package(module):
    above = {
        name for name in imported_modules(os.path.join(OPS, module))
        if name.startswith("narwhal_tpu.")
        and name.split(".")[1] in PROTOCOL
    }
    assert not above, f"ops/{module} reaches up to {sorted(above)}"


def test_only_the_crypto_seam_imports_ops():
    importers = sorted(
        os.path.relpath(path, PACKAGE)
        for path in package_files()
        if not path.startswith(OPS + os.sep)
        and any(
            name == "narwhal_tpu.ops" or name.startswith("narwhal_tpu.ops.")
            for name in imported_modules(path)
        )
    )
    assert importers == [SEAM]
