"""``python -m repro`` — the campaign CLI over the ``Campaign`` facade.

Subcommands::

    python -m repro run examples/pipelines/smoke.yml --store S [--gate]
    python -m repro validate examples/pipelines/smoke.yml
    python -m repro components
    python -m repro daemon examples/pipelines/continuous.yml --store S
    python -m repro daemon-status examples/pipelines/continuous.yml --store S
"""

import sys

from repro.core.api import main
from repro.launch.cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
