"""What every benchmark process sets before JAX starts: the compile
cache inside the checkout (given to the program through
``JAX_COMPILATION_CACHE_DIR``, which ``repro.utils.compile_cache``
honours) and the TPU runtime's logs under the temporary directory, so a
run writes nowhere else."""
import os
import tempfile
from pathlib import Path


def prepare(root: Path) -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
