"""Field sources: one file each, found by a configuration's `field_source`
(chipbench/README.md, "A field source")."""

DEFAULT = "sim_gray_scott"      # a configuration that names none
