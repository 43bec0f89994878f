"""One module per model family, found by the name a configuration's file
gives (`"work"`): the operations and bytes the family's step needs, from
the widths of the configuration and the sizes of the mix. A work module
imports nothing of the program."""
