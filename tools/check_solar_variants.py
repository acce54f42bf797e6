#!/usr/bin/env python3
"""What fails the Solar-Open2 reference check, on the device.

    python3 tools/check_solar_variants.py [--rehearse] [--layers N]

``tools/check_reference_variants.py --config solar-open2-250b``, under the
name that ``benchmark/families/solar_open2.py`` and the verify skill give:
the factor 2 on beta, the decay, a conv tap, the KDA layer's output gate or
the GQA layer's gate left out, a bfloat16 recurrent state, float8 and (as no
fault) bfloat16 matmul inputs. That tool's docstring says what is run and
what fails it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_reference_variants  # noqa: E402


def main(argv=None) -> int:
    return check_reference_variants.main(argv, config="solar-open2-250b")


if __name__ == "__main__":
    sys.exit(main())
