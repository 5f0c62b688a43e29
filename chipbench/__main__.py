import sys
import time

T0 = time.perf_counter()  # the set-up clock starts before jax is imported

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
