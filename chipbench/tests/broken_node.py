"""A primary whose verifier's answer is altered where it is produced:
every mask it returns is all true.  ``test_run_faults.py`` starts primary
0 through this in place of the product's entry point."""

import sys

from narwhal_tpu.crypto import backend
from narwhal_tpu.node.main import main


async def accept_everything(messages, keys, sigs, site="other"):
    return [True] * len(messages)


backend.averify_batch_mask = accept_everything
sys.exit(main(sys.argv[1:]))
